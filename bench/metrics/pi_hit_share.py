"""Share (%) of the window's answered requests served by a pattern-index
hit over the replica modules (``QueryStats.mode == "parallel-replica"``)."""


def read(record):
    done = [r for r in record["requests"] if r.status == "answered"]
    if not done:
        return None
    hits = sum(1 for r in done if r.mode == "parallel-replica")
    return 100.0 * hits / len(done)
