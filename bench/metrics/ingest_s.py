"""Seconds the program spent ingesting the ratings: the sum of its
``ingest.*`` spans in the registry (``ingest.sort``: the (block, row,
col) lexsort and the CSR/CSC build on the host; ``ingest.upload``: the
transfer to the device).  Ingest runs in set-up, so this moves
``setup_s``; ``None`` where the program records no such span."""

PREFIX = "span_seconds{name=ingest."


def read(run):
    hists = run["obs"].get("histograms", {})
    sums = [h["sum"] for k, h in hists.items()
            if k.startswith(PREFIX) and h.get("count")]
    return sum(sums) if sums else None
