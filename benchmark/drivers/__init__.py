"""Traffic drivers, one module a kind, found by the `driver` key of a
traffic file.  A module has a class `Driver(ctx)` (ctx: `harness.Ctx`)
with:

    segment_starts() -> [int]   first rounds of the segments it verifies in
    async setup()               stores, servers: counted as set-up
    async warmup()              drives every program the window will
    async operate() -> dict     one timed operation: ok, rounds, wall_s,
                                spans {name: seconds}, stats (optional)
    end_to_end(records, elapsed_s) -> {metric name: value}
    async check_window(records) -> {name: count}   each has to be 0
    async check_faulted(draw) -> {name: count}   the pass over the chain
                                with `draw`'s faults planted; each 0
    async close()
"""
