"""Mean time a request waited in the serving queue, from the program's
``queue_wait_seconds`` histogram (sum / count; its quantiles are bucket
interpolations and are not used), reset after warm-up."""


def read(run):
    h = run["obs"].get("histograms", {}).get("queue_wait_seconds")
    if not h or not h.get("count"):
        return None
    return 1e3 * h["sum"] / h["count"]
