"""Jobs: what a traffic mix's `job` names. A job's ``run(ctx)`` sets the
system up, calls ``ctx.open_window()``, drives the load for
``ctx.window_seconds()``, calls ``ctx.close_window()``, checks the outputs
and returns

    {"end_to_end": {metric: value}, "attempted": n, "failed": n,
     "checks": checks.Compared, "counters": {...}, "work": {...},
     "arch": {...}}

`checks` holds every number the run was compared on beside its limit
(`jobs/checks.py`); `correct` is true when each is within it.
"""
