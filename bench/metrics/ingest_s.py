"""Seconds of ``AdHashEngine.ingest_stream`` until the store is on the
device (``block_until_ready``), on the benchmark's host clock."""


def read(record):
    return record["ingest_s"]
