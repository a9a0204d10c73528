"""Mean time from the inject of the tail's requests to their first token
reaching the frontend (the program's per-request `first_token_lag`
span): the device's prefill and the overlapped round's one-round delay.
The tail is the measured requests answered before the window's end,
where the profiler stops and stalls the host, whose time to first token
ranks at or above the 75th percentile of theirs."""
import program_telemetry


def read(ctx):
    return program_telemetry.tail_mean_ms(ctx, "first_token_lag")
