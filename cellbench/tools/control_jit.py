#!/usr/bin/env python3
"""tools/control.py for a configuration whose weights leave the chip little
room: the same control (the reference with 8-bit weights in the program's
place, the same leaves, the same judge and limits, the same exit code), with
one thing moved: a stacked leaf's layer is raised to float32 and rounded
inside ONE jitted call.

    python3 cellbench/run.py --workload <cell> --seed N --seconds 1 --trace 0 --check-only
    python3 cellbench/tools/control_jit.py --workload <cell> --seed N

Eager, `control.fake_quant` leaves four float32 temporaries of the leaf
beside its result (the absolute value, the quotient, the rounded and the
clipped copy). Beside `mimo-v2.5-7l`'s 11.69 GB of weights a routed layer's
expert bank is 1.07 GB in float32 and the chip's 16.9 GB ran out
(RESOURCE_EXHAUSTED allocating 1.00G with 142M free: PERF.md section 6, PR
55, call 2). Jitted, the arithmetic is `control.fake_quant`'s own (the same
8-bit levels of the same scales; a compiled product may differ from the eager
one in float32's last place) and the only float32 copy is the result. Every
other configuration's control runs through tools/control.py as it did; this
file adds a way in and edits none.
"""

from __future__ import annotations

import sys

import control


class JitQuantizedLeaf(control.QuantizedLeaf):
    """`control.QuantizedLeaf` whose layer l is rounded by one compiled
    program (a program a leaf shape and bit width, compiled once)."""

    _round = None

    def __getitem__(self, l):
        cls = type(self)
        if cls._round is None:
            import jax
            import jax.numpy as jnp

            cls._round = staticmethod(jax.jit(
                lambda w, bits: control.fake_quant(w.astype(jnp.float32), bits),
                static_argnums=(1,)))
        return cls._round(self.leaf[l], self.bits)


def main() -> int:
    control.QuantizedLeaf = JitQuantizedLeaf  # what `control.quantized` builds
    return control.main()


if __name__ == "__main__":
    sys.exit(main())
