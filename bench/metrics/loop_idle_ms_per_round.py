"""Device-idle time inside the Wave loop's ``fit.round`` spans (key split,
wave order and its host read, the step dispatches) per round of the
traced window, in ms, the mean over the chips used."""

import program_spans


def read(run):
    return program_spans.idle_ms_per_round(run, "fit.round")
