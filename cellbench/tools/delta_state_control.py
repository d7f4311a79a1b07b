#!/usr/bin/env python3
"""tools/state_control.py for a reference whose recurrent state is folded by
the delta rule (`delta_block(S, q, k, v, g, beta)`:
cellbench/reference/delta_hybrid_moe.py): the plain reference with its matrix
state rounded to bfloat16 after every token, put in the program's place. A
reading, not a control that must fail: it says how far a bfloat16 state moves
the check's three numbers and whether the limits a sound server passes would
catch it (`caught=True` on the last line); where they do not, what holds the
state to float32 is the `lin` leaf's dtype pinned in
tests/test_chip_compile.py and tests/cell_program_checks.py and the 2e-4
bound against the float64 recurrence in tests/test_solar_ops.py.

    python3 cellbench/run.py --workload <cell> --seed N --seconds 1 --trace 0 --check-only
    python3 cellbench/tools/delta_state_control.py --workload <cell> --seed N

The same judge, limits and sequences as tools/state_control.py, which this
file calls with the delta rule's block in `scan_block`'s place; it edits
nothing that is there.
"""

from __future__ import annotations

import sys

import state_control


def rounded_delta(S, q, k, v, g, beta):
    """`delta_block` of reference/delta_hybrid_moe.py with the state held in
    bfloat16 between tokens (read back to float32 for the arithmetic)."""
    import jax
    import jax.numpy as jnp

    def step(S, t):
        qt, kt, vt, gt, bt = t
        S = jnp.exp(gt)[:, :, None] * S.astype(jnp.float32)
        err = vt - jnp.einsum("hd,hdv->hv", kt, S)
        S = (S + bt[:, None, None] * kt[:, :, None] * err[:, None, :]).astype(jnp.bfloat16)
        return S, jnp.einsum("hd,hdv->hv", qt, S.astype(jnp.float32))

    S, o = jax.lax.scan(step, S.astype(jnp.bfloat16), (q, k, v, g, beta))
    return S.astype(jnp.float32), o


class _DeltaAsScan:
    """The reference module as `state_control.main` asks for it: its
    `scan_block` IS the reference's `delta_block`."""

    def __init__(self, ref):
        object.__setattr__(self, "_ref", ref)

    def __getattr__(self, name):
        return getattr(self._ref, "delta_block" if name == "scan_block" else name)

    def __setattr__(self, name, value):
        setattr(self._ref, "delta_block" if name == "scan_block" else name, value)


def main() -> int:
    start = state_control.ref_child.start

    def start_as_scan(*args):
        config, ref, params = start(*args)
        return config, _DeltaAsScan(ref), params

    state_control.ref_child.start = start_as_scan
    state_control.rounded_scan = rounded_delta
    return state_control.main()


if __name__ == "__main__":
    sys.exit(main())
