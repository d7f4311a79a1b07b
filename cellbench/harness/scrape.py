"""Read the program's `/metrics` (Prometheus text) into numbers, and take
deltas between two scrapes: counters are sound as counts, and are read here
only as the difference over the window."""

from __future__ import annotations

import re

_LINE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> dict:
    """{(name, (("label", "value"), ...)): float}"""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _LINE.match(line.strip())
        if not m:
            continue
        name, labels, value = m.groups()
        key = tuple(sorted(_LABEL.findall(labels or "")))
        try:
            out[(name, key)] = float(value)
        except ValueError:
            continue
    return out


def total(scrape: dict, name: str, **labels) -> float:
    """Sum of every series of `name` whose labels include `labels`."""
    want = set(labels.items())
    return sum(
        v for (n, key), v in scrape.items() if n == name and want <= set(key)
    )


def delta(before: dict, after: dict, name: str, **labels) -> float:
    return total(after, name, **labels) - total(before, name, **labels)
