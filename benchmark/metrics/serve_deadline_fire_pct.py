"""Share of the window's dispatches whose bucket fired because its
oldest request ran out of slack (``serve_dispatches{reason=deadline}``;
the other reasons are ``full``, ``flush`` and ``backpressure``).  None
where the program does not label its dispatches with a reason."""


def read(ctx):
    fired = {k: v for k, v in ctx.telemetry.d.items()
             if k.startswith("serve_dispatches{") and "reason=" in k}
    total = sum(fired.values())
    if not total:
        return None
    deadline = sum(v for k, v in fired.items() if "reason=deadline" in k)
    return 100.0 * deadline / total
