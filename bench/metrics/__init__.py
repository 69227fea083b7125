"""One reader per per-layer metric: ``<name>.py`` with ``read(record)``."""
