"""Host time per router tick: the host clock over every tick of the
window, less the time the admit and decode calls spent waiting for the
device, over the number of ticks."""


def read(run):
    if not run.ticks:
        return None
    return (run.tick_host_s - run.device_wait_s) / run.ticks * 1e3
