"""Device-idle time inside the Wave loop's ``fit.cost`` spans (the cost
at each eval boundary and its host read) per round of the traced window,
in ms, the mean over the chips used."""

import program_spans


def read(run):
    return program_spans.idle_ms_per_round(run, "fit.cost")
