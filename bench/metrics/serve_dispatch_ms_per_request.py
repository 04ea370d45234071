"""Host time per request of padding each chunk and calling its bucket
executable: the program's ``serve.dispatch`` spans, summed, ÷ the
``serve.request`` count, in ms."""

import program_spans


def read(run):
    return program_spans.ms_per_request(run, "serve.dispatch")
