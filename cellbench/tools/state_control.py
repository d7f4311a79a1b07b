#!/usr/bin/env python3
"""An instrument beside the output check, for a configuration that STATES a
float32 recurrent state: the plain reference with its matrix state rounded
to bfloat16 after every token, put in the program's place. It reads how far
a bfloat16 state moves the check's three numbers, and says whether the
limits a sound server passes would catch it. It is a reading, not a control
that must fail: in `granite-batch` it PASSES (3 seeds, my chip runs, PR 51:
under the sound bfloat16 program's own readings in two, failing `worst` by
a hair in the third), because the program's bfloat16 weights and activations
move the choices more than a rounded state does. What holds the state to
float32 there is the `lin` leaf's dtype pinned in tests/test_chip_compile.py
and tests/cell_program_checks.py, and the 2e-4 bound against the float64
recurrence in tests/test_granite_ops.py (PERF.md question 31): a claim in
such a cell shows `pool["lin"]` float32 beside its numbers.

    python3 cellbench/run.py --workload <cell> --seed N --seconds 1 --trace 0 --check-only
    python3 cellbench/tools/state_control.py --workload <cell> --seed N

As tools/control.py (whose 8-bit weights ARE the control, and must fail): it
reads the check's sequences the first command left, runs the reference
teacher-forced over each twice, as it is (the judge) and with the state
rounded, and holds the rounded pass's own picks to the judge's logits as
the server's tokens are held. Exit code 0 once it has read; the last line
says `caught=True` where the limits fail the rounded state.

The rounding wraps the reference from outside: `reference/` stays plain. It
takes the place of the reference's `scan_block(S, x, dt, A, B, C)` (the
recurrence over a block of tokens: cellbench/reference/ssm_hybrid.py); a
reference without one has no state this tool knows how to round.
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


def rounded_scan(S, x, dt, A, B, C):
    """`scan_block` of reference/ssm_hybrid.py with the state held in
    bfloat16 between tokens (read back to float32 for the arithmetic)."""
    import jax
    import jax.numpy as jnp

    def step(S, t):
        xt, dtt, Bt, Ct = t
        S = jnp.exp(dtt * A)[:, None, None] * S.astype(jnp.float32) + (
            (dtt[:, None] * xt)[:, :, None] * Bt[None, None, :])
        S = S.astype(jnp.bfloat16)
        return S, jnp.einsum("hpn,n->hp", S.astype(jnp.float32), Ct)

    S, y = jax.lax.scan(step, S.astype(jnp.bfloat16), (x, dt, B, C))
    return S.astype(jnp.float32), y


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
    if not hasattr(ref, "scan_block"):
        raise SystemExit(f"reference {config['reference']!r} has no scan_block to round")
    plain = ref.scan_block
    rows = []
    for seq in seqs:
        judge = ref_child.generated_logits(ref, config, params, seq)
        ref.scan_block = rounded_scan
        try:
            pick = ref_child.generated_logits(ref, config, params, seq).argmax(axis=-1)
        finally:
            ref.scan_block = plain
        rows.append({"name": seq["name"], "n_prompt": seq["n_prompt"],
                     "margins": ref_child.margins(judge, pick)})
    say = lambda msg: print(msg, flush=True)  # noqa: E731
    say(f"state reading: the reference with a bfloat16 matrix state in the program's place, "
        f"{cell.name} seed {args.seed}, limits {json.dumps(config['check'])}")
    passed = check.judge(seqs, {"sequences": rows}, config["check"], say)
    say(f"state reading: caught={not passed} (the check's limits "
        f"{'fail' if not passed else 'do NOT fail'} a bfloat16 state)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
