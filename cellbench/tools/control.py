#!/usr/bin/env python3
"""The control of the output check: the plain reference, computed with
8-bit weights, put in the program's place. Its choices must come out as
NOT correct under the same limits that a sound server passes; if they pass,
the limits are too loose to see the precision step below bfloat16.

    python3 cellbench/run.py --workload <cell> --seed N --seconds 1 --trace 0 --check-only
    python3 cellbench/tools/control.py --workload <cell> --seed N

The first command leaves the check's sequences (prompt + the tokens the
server generated) in `.cellbench/check/<cell>.seed<N>.trace0/check_in.json`;
this tool reads them after the server has gone. It runs the reference
teacher-forced over each sequence twice: as it is (the judge), and with every
weight matrix quantized to 8 bits (symmetric, one scale per output channel,
weight only: what an int8 weight path would multiply by). At each
generated position the quantized pass picks its own best token, and that
pick is held to the judge's logits exactly as the server's token is. Exit
code 0 if the control fails the check (as it must), 1 if it passes.

The low-precision pass lives here and wraps the reference from outside:
`reference/` stays plain. The program's own `--quant int8` cannot serve as
the control at these sizes (it cannot load a 7 GB model on one chip, PERF.md
section 6); at the tiny size of the tests it can, and both controls are
tests in cellbench/tests.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

from harness import check, launcher, ref_child  # noqa: E402
from harness.manifest import ROOT, Cell, load_json  # noqa: E402

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
BITS = 8  # the precision step below the bfloat16 the configurations state


def fake_quant(w, bits: int):
    """A float32 matrix [in, out] rounded to `bits`-bit integers times one
    scale per output channel, back in float32."""
    import jax.numpy as jnp

    top = 2.0 ** (bits - 1) - 1.0
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-12) / top
    return jnp.clip(jnp.round(w / scale), -top, top) * scale


class QuantizedLeaf:
    """A stacked leaf [L, in, out] whose layer l comes out quantized, in
    float32, when the reference asks for it (one layer at a time: the whole
    tree in float32 would not fit the chip)."""

    def __init__(self, leaf, bits: int):
        self.leaf, self.bits = leaf, bits

    def __getitem__(self, l):
        import jax.numpy as jnp

        return fake_quant(self.leaf[l].astype(jnp.float32), self.bits)


def quantized(params: dict, bits: int) -> dict:
    import jax.numpy as jnp

    out = {k: QuantizedLeaf(v, bits) if k in MATRICES else v for k, v in params.items()}
    out["lm_head"] = fake_quant(params["lm_head"].astype(jnp.float32), bits)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    cell = Cell(load_json(args.manifest), args.workload)
    d = os.path.join(launcher.state_dir(), "check", f"{cell.name}.seed{args.seed}.trace0")
    with open(os.path.join(d, "check_in.json")) as f:
        seqs = json.load(f)["sequences"]
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".xla_cache")
    config, ref, params = ref_child.start(cell.config_path, args.seed, cache)
    low = quantized(params, BITS)
    rows = []
    for seq in seqs:
        judge = ref_child.generated_logits(ref, config, params, seq)
        pick = ref_child.generated_logits(ref, config, low, seq).argmax(axis=-1)
        rows.append({"name": seq["name"], "n_prompt": seq["n_prompt"],
                     "margins": ref_child.margins(judge, pick)})
    say = lambda msg: print(msg, flush=True)  # noqa: E731
    say(f"control: the reference with {BITS}-bit weights in the program's place, "
        f"{cell.name} seed {args.seed}, limits {json.dumps(config['check'])}")
    passed = check.judge(seqs, {"sequences": rows}, config["check"], say)
    say(f"control: correct={passed} (it must be False)")
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
