"""Kernels: the least time the chip needs to read the K and V the traced
launches' rows attend, over the attention kernels' device time in those
launches. The launches are the `launch.*` spans of the trace matched with
the executions of their step programs (harness/host_spans.join_launches);
each carries `kv_tokens`, KV positions per layer and KV head by the
program's own position model, so bytes = kv_tokens x layers x 2 x KV heads
x head dim x bytes per number, against the peak HBM bandwidth. Bandwidth-
bound at these shapes (roofline/ragged_attention.py), and a lower bound on
the bytes (a prefill chunk is counted once, the kernel reads it per query
tile), so it understates. In every cell, where `ragged_attn_roofline.batch`
needs the load generator's memory of its own requests."""
from harness import host_spans, manifest, trace_reduce


def read(ctx):
    path = host_spans.find(ctx.trace_dir)
    if path is None:
        return None
    spans = host_spans.read(path)
    names = ctx.config["serving"]["trace"]
    planes = trace_reduce.read_planes(path)
    if not spans or not planes:
        return None
    chip = planes[min(planes)]
    matched = host_spans.join_launches(
        spans, chip.get(trace_reduce.MODULES_LINE, []), names["step_modules"])
    if not matched:
        return None
    ra = manifest.load_module("roofline", "ragged_attention")
    L, _, KV, Dh, _ = ra._dims(ctx.config)
    tokens = sum(int(stats["kv_tokens"]) for stats, _, _ in matched)
    least = tokens * L * 2 * KV * Dh * ra.BYTES[ctx.config.get("torch_dtype", "bfloat16")] \
        / ctx.peaks["hbm_bytes_per_s"]
    inside = sorted((s, e) for _, s, e in matched)
    kernel_s = sum(
        e - s for name, s, e in chip.get(trace_reduce.OPS_LINE, [])
        if any(k in trace_reduce.op_name(name) for k in names["attention_kernels"])
        and any(a <= s < b for a, b in inside))
    return 100.0 * least / kernel_s if kernel_s > 0 else None
