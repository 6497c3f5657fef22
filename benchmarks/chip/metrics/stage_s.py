"""Seconds of set-up spent building the job through ``repro.api.build``
and staging its shards (data generation, stacking and, on the kernel
path, densification), on the host clock, ending in
``block_until_ready``."""


def read(run):
    return run.stage_s
