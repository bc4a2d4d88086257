"""Shared servable construction for image classifiers.

Replaces the reference's ``predict()`` (decode → transforms → forward →
softmax → top-k, SURVEY §3.2) with a split that is TPU-shaped: host does
decode/resize/crop to **uint8** (4x less PCIe traffic than fp32), the device
program fuses normalize + forward + softmax into one XLA executable, host does
the final top-k label lookup.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ModelConfig
from ..engine.servable import Servable
from ..ops.preprocessing import normalize_on_device, preprocess_image_bytes_uint8
from ..utils.labels import load_labels


def resolve_dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}[name]


def cast_params_at_rest(params, dtype):
    """At-rest weight cast: only ≥2-D fp32 leaves convert — LayerNorm/BN
    scales and biases stay fp32 for the fp32 norm paths.

    THE single definition of the at-rest predicate; engine/compiled.py (the
    serving path), the tools that time a model outside the server
    (tools/trace_ops.py, tools/profile_sd15.py) and the gpt2 int8 lane all
    call it, so a measurement cannot silently diverge from serving again
    (r2's sd15 was timed fp32-at-rest by exactly this drift).
    """
    import jax

    return jax.tree.map(
        lambda x: x.astype(dtype)
        if (getattr(x, "dtype", None) == jnp.float32
            and getattr(x, "ndim", 0) >= 2) else x,
        params)


def make_image_classifier(name: str, module, cfg: ModelConfig,
                          convert_fn: Callable | None,
                          image_size: int = 224, resize_to: int = 256,
                          num_classes: int = 1000, norm_mean=None,
                          norm_std=None, tp_rules=None) -> Servable:
    """module: a flax Module taking normalized NHWC floats → logits."""
    from ..engine import weights as W

    image_size = int(cfg.extra.get("image_size", image_size))
    resize_to = int(cfg.extra.get("resize_to", resize_to))
    norm_mean = cfg.extra.get("norm_mean", norm_mean)
    norm_std = cfg.extra.get("norm_std", norm_std)
    if cfg.checkpoint:
        if convert_fn is None and not W.is_native(cfg.checkpoint):
            raise ValueError(f"{name}: no checkpoint converter available")
        params = W.import_params(cfg.checkpoint, convert_fn)
    else:
        dummy = jnp.zeros((1, image_size, image_size, 3), jnp.float32)
        params = module.init(jax.random.key(0), dummy)["params"]
    params = jax.device_put(params)  # ONE batched tree transfer: per-leaf jnp.asarray
    # serializes a host round-trip per buffer.
    labels = load_labels(cfg.extra.get("labels"), num_classes)
    if len(labels) < num_classes:
        raise ValueError(f"{name}: labels file has {len(labels)} entries, "
                         f"model has {num_classes} classes")
    topk = int(cfg.extra.get("topk", 5))

    def apply_fn(p, inputs):
        x = normalize_on_device(inputs["image"], norm_mean, norm_std)
        logits = module.apply({"params": p}, x)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        # Top-k on device, packed into ONE small array: a single D2H fetch per
        # batch (each separate output buffer costs a fetch round-trip, and
        # the 1000-way softmax readback is saved too).
        values, idx = jax.lax.top_k(probs, topk)
        return {"topk_packed": jnp.concatenate(
            [values, idx.astype(jnp.float32)], axis=-1)}

    def input_spec(bucket):
        return {"image": jax.ShapeDtypeStruct((bucket[0], image_size, image_size, 3),
                                              jnp.uint8)}

    def preprocess(payload) -> dict:
        if isinstance(payload, (bytes, bytearray)):
            return {"image": preprocess_image_bytes_uint8(bytes(payload), resize_to, image_size)}
        # Pre-decoded array path (tests / batch API): HWC uint8.
        arr = np.asarray(payload, dtype=np.uint8)
        if arr.shape != (image_size, image_size, 3):
            raise ValueError(f"expected {(image_size, image_size, 3)} uint8, got {arr.shape}")
        return {"image": arr}

    def postprocess(out, i):
        packed = out["topk_packed"][i]
        values, idx = packed[:topk], packed[topk:].astype(int)
        return {"top_k": [{"label": labels[int(j)], "index": int(j),
                           "prob": float(v)} for v, j in zip(values, idx)]}

    from ..parallel.mesh import CNN_HEAD_TP_RULES

    return Servable(name=name, apply_fn=apply_fn, params=params, input_spec=input_spec,
                    preprocess=preprocess, postprocess=postprocess,
                    bucket_axes=("batch",),
                    meta={"num_classes": num_classes,
                          "tp_rules": (CNN_HEAD_TP_RULES if tp_rules is None
                                       else tp_rules)})
