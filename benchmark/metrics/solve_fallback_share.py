"""Share of the ALS update's mode solves inside the timed ``cpd_als``
call that took the pseudoinverse path (the normal matrix numerically
singular) rather than Cholesky, in percent: the call's solve counts as
its ``cpd.als`` span records them (``solve``: path -> per-mode counts,
with tracing on, as in a ``--trace 1`` run).  Prints the counts per
mode, the call's largest condition estimate (``cond_max``) and its
smallest factorization margin (``margin_min``: above 1 Cholesky ran)
for the log.  A program whose root span carries no solve counts gives
nothing."""


def read(ctx):
    from splatt_tpu import trace

    roots = [s["args"] for s in trace.spans("cpd.als")
             if "solve" in s["args"]]
    if not roots:
        return None
    solve = roots[-1]["solve"]
    pinv = sum(solve.get("pinv", []))
    total = pinv + sum(solve.get("cholesky", []))
    print(f"bench: window solves per mode {solve}; largest condition "
          f"estimate {roots[-1].get('cond_max')!r}; smallest "
          f"factorization margin {roots[-1].get('margin_min')!r}",
          flush=True)
    return 100.0 * pinv / total if total else None
