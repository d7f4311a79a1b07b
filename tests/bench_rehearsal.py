"""What the tests that rehearse a benchmark cell on the CPU share: the cell's
tiny manifest at a rate a starved machine still serves.

The rehearsals run `cellbench/run.py` as a child beside five other workers of
the suite. At the tiny cells' own rates (2 sessions/s) a server that gets a
fifth of a core falls behind its open-loop arrivals, its admission control
sheds what it cannot start inside the class's TTFT target
(engine/scheduler.should_shed: `slo_shed`, status `failed`), and the
rehearsal's "0 failed" fails for the machine's load, not for the program
(the driver's run of PR 47's tree: 1 of 33). A rehearsal is of the phases,
not of a rate: `light_manifest` copies the manifest with the cell's rate
lowered and everything else as the data files under cellbench/ have it.
"""

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def light_manifest(tmp_path, manifest_path: str, cell: str, rate: float) -> str:
    """The path of a copy of `manifest_path` under tmp_path whose `cell`
    offers `rate` sessions/s: the traffic files copied, the configurations'
    files where they are (absolute paths), the cell's file rewritten."""
    with open(manifest_path) as f:
        manifest = json.load(f)
    data = os.path.join(ROOT, manifest["paths"][0])
    out = os.path.join(str(tmp_path), "bench")
    shutil.copytree(os.path.join(data, "traffic"), os.path.join(out, "traffic"))
    os.makedirs(os.path.join(out, "cells"))
    with open(os.path.join(out, "cells", f"{cell}.json"), "w") as f:
        json.dump({"load": {"rate": rate}}, f)
    manifest["paths"] = [out]
    for config in manifest["configs"]:
        config["file"] = os.path.join(ROOT, config["file"])
    path = os.path.join(str(tmp_path), "BENCHMARK.light.json")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return path
