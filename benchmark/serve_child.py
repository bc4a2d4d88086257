"""The server process of a run: the program's own ``tpuserve serve`` entry
point, unchanged, in a process that owns the chip.  When the server has
stopped it writes the device's peak memory to the file named first, which
only the process that held the chip can read."""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    from pytorch_zappa_serverless_tpu import cli

    out, argv = Path(sys.argv[1]), sys.argv[2:]
    try:
        return cli.main(argv)
    finally:
        import jax

        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        out.write_text(json.dumps({
            "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                     for s in stats),
            "bytes_limit": max(int(s.get("bytes_limit", 0)) for s in stats)}))


if __name__ == "__main__":
    sys.exit(main())
