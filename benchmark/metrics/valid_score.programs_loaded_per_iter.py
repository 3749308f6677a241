"""The package's ``program_load`` events whose innermost span is
``valid_score``, in the window's iterations, per iteration.

The accepted ``loop.programs_loaded_per_iter`` counts every load of the
window with the harness's own listener and is the one to keep as the total;
this one says under which span the loads fall, and reads the same wherever
validation scoring is the only loader (PERF.md section 5)."""


def read(ctx):
    loads = [e for e in ctx.obs_events if e.get("type") == "program_load"]
    if not loads:
        return None      # the program has no such counter, or it is off
    first = ctx.window.warmup + 1
    n = sum(1 for e in loads if e.get("span") == "valid_score"
            and first <= e.get("iteration", 0)
            < first + ctx.window.window_iters)
    return n / ctx.window.window_iters
