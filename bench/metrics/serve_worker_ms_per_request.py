"""Time the serving worker spends on a request, from taking it up to
resolving its future: the program's ``serve.request`` span, sum ÷ count,
in ms."""

import program_spans


def read(run):
    return program_spans.ms_per_request(run, "serve.request")
