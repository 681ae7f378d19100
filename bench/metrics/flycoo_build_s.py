"""flycoo_build_s (s): the FLYCOO build's own wall time, once per tensor,
as the program counts it (counter ``flycoo.build_s`` of ``repro.obs``):
the host part of ``preprocess_s`` before the runtime build. Read beside
a device trace only, like the metrics of the chip's layers."""
import phases


def read(ctx):
    return phases.program_counter(ctx, "flycoo.build_s")
