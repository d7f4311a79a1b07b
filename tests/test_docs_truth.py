"""The ROADMAP's standing constraint, enforced: outside the records that
carry origins (PERF.md, CHANGES.md, ROADMAP.md, PERF_LEDGER.jsonl), the
reference's own survey (SURVEY.md) and the benchmark (cellbench/), no
line states a speed the ledger does not bear out. Where the chip has not
spoken the text says what the code does and "not measured"."""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_RATIO = r"(?<![\w.])\d+(?:\.\d+)?\s*[x×](?!\w)"
_CLAIMS = [
    # a number beside tok/s ("321 tok/s"; a printed `{...} tok/s` label
    # carries no number and passes)
    re.compile(r"\d[\d.,]*\+?\s*tok/s"),
    # a ratio beside "on v5e" / "faster" / "slower" / "speedup"
    re.compile(
        rf"(?i)(?:{_RATIO}.*(?:on v5e|faster|slower|speed-?up))"
        rf"|(?:(?:on v5e|faster|slower|speed-?up).*{_RATIO})"
    ),
    # "measured ... on v5e", either way round
    re.compile(r"(?i)measured\b.*\bon v5e|\bon v5e\b.*\bmeasured"),
    # a share of a roofline
    re.compile(r"(?i)\d\s*%\s+of the (?:\w+ ){0,2}roofline"),
]
_ORIGIN = re.compile(r"\(ledger, PR \d+\)")


def _files(case):
    if case == "package":
        return sorted(glob.glob(
            os.path.join(ROOT, "distributed_llm_inference_tpu", "**", "*.py"),
            recursive=True,
        )) + [os.path.join(ROOT, "chip_smoke.py")]
    return [os.path.join(ROOT, case)]


@pytest.mark.parametrize("case", [
    "README.md", "ARCHITECTURE.md", "package",
    ".claude/skills/verify/SKILL.md",  # what every builder reads first
])
def test_no_speed_number_without_a_ledger_origin(case):
    bad = []
    for path in _files(case):
        with open(path, encoding="utf-8") as f:
            for n, line in enumerate(f, 1):
                if _ORIGIN.search(line):
                    continue
                if any(p.search(line) for p in _CLAIMS):
                    bad.append(
                        f"{os.path.relpath(path, ROOT)}:{n}: {line.strip()}"
                    )
    assert not bad, (
        "speed figures with no ledger origin (delete them, or say what "
        "the code does and 'not measured'):\n" + "\n".join(bad)
    )
