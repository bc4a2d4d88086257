"""Write a configuration's seeded weights once, as the staged checkpoint the
server boots from (``checkpoint:``, the product's activation path).

Run as a child with ``JAX_PLATFORMS=cpu``: it imports the program's builder
(which imports JAX) and must not take the chip.  The tree comes from the
program's own ``init_gpt2_params``; matrices are kept in ``dtype``
(bfloat16 where the server holds them so, float32 where it quantizes them
itself), vectors in float32, as the server's at-rest cast leaves them.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    out, dtype, seed = Path(sys.argv[1]), sys.argv[2], int(sys.argv[3])
    arch = {k: int(v) for k, v in (a.split("=") for a in sys.argv[4:])}
    import ml_dtypes
    import numpy as np

    from pytorch_zappa_serverless_tpu.engine.weights import save_native
    from pytorch_zappa_serverless_tpu.models.gpt2 import (GPT2Config,
                                                          init_gpt2_params)

    tree = init_gpt2_params(seed, GPT2Config(**arch))
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
    sys.exit(main())
