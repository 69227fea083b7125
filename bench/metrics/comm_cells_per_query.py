"""Communication cells per answered request in the window: the sum of
``QueryStats.comm_cells`` over the answered requests, divided by them."""


def read(record):
    done = [r for r in record["requests"] if r.status == "answered"]
    return sum(r.comm_cells for r in done) / len(done) if done else None
