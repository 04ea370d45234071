"""Time per request of copying each chunk's answer back to the host,
which waits for the chunk's device work: the program's ``serve.fetch``
spans, summed, ÷ the ``serve.request`` count, in ms."""

import program_spans


def read(run):
    return program_spans.ms_per_request(run, "serve.fetch")
