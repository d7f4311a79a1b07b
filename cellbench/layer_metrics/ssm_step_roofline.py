"""Model step: the whole step's share of the memory roofline for a model of
state-space layers. For each traced launch the least bytes its steps stream
(roofline/ssm_scan.step_bytes: the weights once a step, counted by
`layer_types`, plus every row-step's float32 states read and written) at the
peak HBM bandwidth, over the device time of THAT launch's execution of its
step program (harness/host_spans.join_launches pairs them). Useful bytes
only (no K/V, no activation, no padding of the flat axis), so it cannot pass
100; a mixed step whose flat tokens make it compute-bound reads low, and
that is the finding. The bound a later claim in such a cell is read
against. From a configuration without `mamba_n_heads`, or a program or a
trace without the launch spans or the record's `state_rows`, None."""
from harness import host_spans, manifest, trace_reduce


def read(ctx):
    trace = ctx.config.get("serving", {}).get("trace", {})
    path = host_spans.find(ctx.trace_dir)
    if "mamba_n_heads" not in ctx.config or path is None or "step_modules" not in trace:
        return None
    spans = host_spans.read(path)
    planes = trace_reduce.read_planes(path)
    if not spans or not planes:
        return None
    chip = planes[min(planes)]
    matched = [m for m in host_spans.join_launches(
        spans, chip.get(trace_reduce.MODULES_LINE, []), trace["step_modules"])
        if "state_rows" in m[0]]
    seconds = sum(e - s for _, s, e in matched)
    if seconds <= 0:
        return None
    ssm = manifest.load_module("roofline", "ssm_scan")
    least = sum(ssm.step_bytes(ctx.config, st) for st, _, _ in matched)
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / seconds
