"""Mean time the tail's requests waited in the scheduler's queue, from
the scheduler's submit to the slot that admits them (the program's
per-request `sched_queue` span).  The tail is the measured requests
answered before the window's end, where the profiler stops and stalls
the host, whose time to first token ranks at or above the 75th
percentile of theirs."""
import program_telemetry


def read(ctx):
    return program_telemetry.tail_mean_ms(ctx, "sched_queue")
