"""Where a traced run's device idle time falls among the program's
round-phase spans.

    python3 chipbench/phases.py --workload <name> --seed <n> --seconds <s>

Runs the cell as ``run.py --trace 1`` does and prints its result line, then
one more JSON line (``harness/spans.py`` ``report``): device idle seconds
by the innermost ``fl.*`` span open, the share of the window idle with no
span open, and how long after the device's last operation each ``fl.sync``
ends.  A program without the spans reports all its idle time under
``none``."""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    sys.path.insert(0, str(REPO))
    from chipbench import run
    from chipbench.harness import spans, trace
    loaded = []
    load = trace.load

    def keep(trace_dir):
        loaded.append(load(trace_dir))
        return loaded[-1]

    trace.load = keep
    try:
        rc = run.main(list(sys.argv[1:] if argv is None else argv)
                      + ["--trace", "1"])
    finally:
        trace.load = load
    if rc == 0 and loaded:
        print(json.dumps(spans.report(loaded[0])), flush=True)
    return rc


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
