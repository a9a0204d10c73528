"""Share of the decode rows the window's decode chunks ran that belonged
to a request: the rows each chunk decodes (the program's
`sched.decode_rows_active` count, which each `decode_chunk` span carries
as `rows`), over the chunks times the pool's slots.  A chunk runs every
slot whatever its occupancy."""
import program_telemetry


def read(ctx):
    return program_telemetry.decode_row_use(ctx)
