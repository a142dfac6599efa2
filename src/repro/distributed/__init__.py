from repro.distributed import sharding  # noqa: F401
