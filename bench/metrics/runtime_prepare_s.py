"""runtime_prepare_s (s): the runtime build and mode-0 pack's own wall
time, once per tensor, as the program counts it (counter
``runtime.prepare_s`` of ``repro.obs``): the part of ``preprocess_s``
between the FLYCOO build and the copy to the chip. Read beside a device
trace only, like the metrics of the chip's layers."""
import phases


def read(ctx):
    return phases.program_counter(ctx, "runtime.prepare_s")
