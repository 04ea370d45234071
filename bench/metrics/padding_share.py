"""Share of the store's entry slots that hold no rating, 1 - sum of
nnz / (blocks x capacity), from the program's ``ingest_padding_share``
gauge set at ingest: the segment engine pays for every slot, while
``bench/workcount.py`` counts only stored ratings.  ``None`` where the
program sets no such gauge."""


def read(run):
    v = run["obs"].get("gauges", {}).get("ingest_padding_share")
    return None if v is None else float(v)
