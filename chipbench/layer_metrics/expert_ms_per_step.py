"""Layer `kernels`: device milliseconds a step spends in the expert layer's
grouped matrix products (`lax.ragged_dot`: XLA's own grouped-product kernel
and the kernel that builds its tile metadata), forward and backward, found
by name.  The sort, gather and scatter-add around them are XLA fusions
without a name that says whose they are, and are not in this number.  `None`
where the trace has no grouped product."""


def read(run):
    ops = (run["trace"] or {}).get("ops_ms_per_step") or {}
    found = [ms for name, ms in ops.items()
             if "ragged-dot" in name and ms is not None]
    return sum(found) if found else None
