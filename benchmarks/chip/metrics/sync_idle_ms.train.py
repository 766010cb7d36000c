"""Device-idle time per train that lies under the layer loop's
``dssfn.host_sync`` spans (device -> host fetches), as a mean over the
chips (trace: idle gaps of the device against the host spans)."""
from benchmarks.chip import program_trace


def read(r):
    names = program_trace.names()
    return None if names is None else program_trace.sync_idle_ms_per_train(r, names.HOST_SYNC)
