"""Look at one profiler trace by hand: planes, lines, counts, an excerpt.

    python benchmark/tests/dump_trace.py <trace_dir> <out.json> [n_events]

Writes the structure of the newest trace under ``trace_dir`` (what
``run.py --trace 1`` leaves under ``.bench_out/<workload>/``) and the first
``n_events`` events of every device line, in the form
``trace_reduce.reduce_profile`` takes. ``tests/trace_excerpt.json`` was cut
from such a dump of a chip run.
"""

import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)

from benchmark import trace_reduce  # noqa: E402


def main() -> int:
    trace_dir, out_path = sys.argv[1], sys.argv[2]
    n = int(sys.argv[3]) if len(sys.argv) > 3 else 200
    profile = trace_reduce.load_profile(trace_dir)
    if profile is None:
        print(f"no trace under {trace_dir}", file=sys.stderr)
        return 1
    structure = {
        plane: {line: len(events) for line, events in lines.items()}
        for plane, lines in profile.items()
    }
    excerpt = {
        plane: {line: events[:n] for line, events in lines.items()}
        for plane, lines in trace_reduce.device_planes(profile).items()
    }
    reduced = trace_reduce.reduce_profile(profile)
    top = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:30]
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump({
            "structure": structure, "excerpt": excerpt,
            "busy_s": reduced["busy_s"], "modules": reduced["modules"],
            "top_ops": top,
        }, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
