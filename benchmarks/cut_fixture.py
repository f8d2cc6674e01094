"""Cut a few milliseconds out of a profiler capture into a fixture that
``trace_reduce.load_fixture`` reads: how the recorded traces under
``fixtures/`` were made.

    python3 benchmarks/cut_fixture.py <profile dir or .xplane.pb> <from ms> <length ms> <out.json.gz> [note]

``from ms`` counts from the start of the capture's ``bench.window``. The
cut becomes the fixture's own ``bench.window``; the run's other ``bench.*``
annotations that overlap it are kept.
"""

import glob
import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import trace_reduce as tr  # noqa: E402


def main(argv) -> int:
    path, start_ms, length_ms, out = argv[1], float(argv[2]), \
        float(argv[3]), argv[4]
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                recursive=True))[-1]
    trace = tr.load_xplane(path)
    w0, _ = tr.window_of(trace)
    cut = (w0 + start_ms / 1e3, w0 + (start_ms + length_ms) / 1e3)
    trace.host = [o for o in trace.host if o.name != tr.WINDOW] \
        + [tr.Op(tr.WINDOW, *cut)]
    doc = tr.to_json(trace, cut)
    doc["recorded"] = argv[5] if len(argv) > 5 else ""
    with gzip.GzipFile(out, "wb", mtime=0) as f:
        f.write(json.dumps(doc, separators=(",", ":")).encode())
    rows = sum(len(v) for v in doc["devices"].values())
    print(f"{out}: {os.path.getsize(out)} bytes, {rows} device rows on "
          f"{len(doc['devices'])} device(s), {len(doc['host'])} host rows")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
