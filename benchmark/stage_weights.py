"""Write a configuration's seeded weights once, as the staged checkpoint the
server boots from (``checkpoint:``, the product's activation path).

    python3 benchmark/stage_weights.py <out> <configuration file> <serve fragment as JSON>

Run as a child with ``JAX_PLATFORMS=cpu``: the family's ``init_tree``
imports the program's builder (which imports JAX) and must not take the
chip.  Matrices are kept in ``weights.dtype`` (bfloat16 where the server
holds them so, float32 where it quantizes them itself), vectors in float32,
as the server's at-rest cast leaves them.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv: list[str]) -> int:
    out, config = Path(argv[0]), json.loads(Path(argv[1]).read_text())
    serve = json.loads(argv[2])
    import ml_dtypes

    from benchmark import families
    from pytorch_zappa_serverless_tpu.engine.weights import save_native

    dtype, seed = config["weights"]["dtype"], int(config["weights"]["seed"])
    tree = families.load(config).init_tree(seed, config, serve)
    if dtype == "bfloat16":
        def cast(node):
            if isinstance(node, dict):
                return {k: cast(v) for k, v in node.items()}
            return node.astype(ml_dtypes.bfloat16) if node.ndim >= 2 else node
        tree = cast(tree)
    elif dtype != "float32":
        raise SystemExit(f"weights.dtype must be bfloat16 or float32: {dtype}")
    out.parent.mkdir(parents=True, exist_ok=True)
    tmp = out.with_name(f"{os.getpid()}-{out.name}")  # keeps the suffix
    save_native(tree, tmp)
    os.replace(tmp, out)
    print(f"staged {out} ({out.stat().st_size / 1e9:.2f} GB)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
