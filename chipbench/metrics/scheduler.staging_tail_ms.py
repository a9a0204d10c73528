"""Mean time the tail's requests spent admitting, from the slot acquired
to the inject that joins them to the decode pool (the program's
per-request `staging` span): the chunked-prefill segments of a long
prompt, about nothing for a bucketed group.  The tail is the measured
requests answered before the window's end, where the profiler stops and
stalls the host, whose time to first token ranks at or above the 75th
percentile of theirs."""
import program_telemetry


def read(ctx):
    return program_telemetry.tail_mean_ms(ctx, "staging")
