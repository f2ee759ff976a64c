"""Hand-read dump of an ``.xplane.pb``: planes, lines, and the first events of each line with their stats.

    python3 benchmark/tools/dump_xplane.py <file.xplane.pb> [events-per-line]
"""

from __future__ import annotations

import sys


def main(path: str, per_line: int = 3) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:per_line]:
                stats = {k: (v if not isinstance(v, str) or len(v) < 80 else v[:80] + "...") for k, v in ev.stats}
                print(f"    {ev.name[:100]!r} start_ns={ev.start_ns:.0f} dur_ns={ev.duration_ns:.0f} stats={stats}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 3)
