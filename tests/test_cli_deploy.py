"""CLI + deploy-rendering surface."""

import json

import pytest

from pytorch_zappa_serverless_tpu.cli import main
from pytorch_zappa_serverless_tpu.config import ServeConfig
from pytorch_zappa_serverless_tpu.deploy.render import render_deploy


def test_list_models(capsys):
    assert main(["list-models"]) == 0
    out = capsys.readouterr().out.split()
    assert "resnet18" in out and "resnet50" in out


def test_render_deploy(tmp_path):
    cfg = ServeConfig(profile="prod", port=8080)
    summary = render_deploy(cfg, target="cloudrun", out_dir=tmp_path)
    assert set(summary["files"]) == {"Dockerfile", "config.yaml", "service.yaml",
                                     "undeploy.sh", "warmpool.sh"}
    docker = (tmp_path / "Dockerfile").read_text()
    assert "EXPOSE 8080" in docker
    assert "tpuserve-prod" in (tmp_path / "service.yaml").read_text()
    assert json.loads((tmp_path / "deploy.json").read_text())["profile"] == "prod"
    assert "cli warm" in (tmp_path / "warmpool.sh").read_text()
    undeploy = (tmp_path / "undeploy.sh").read_text()
    assert "tpuserve-prod" in undeploy and "delete" in undeploy


def test_warm_cli(tmp_path, capsys, monkeypatch):
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(
        "compile_cache_dir: %s\n"
        "models:\n"
        "  - {name: resnet18, batch_buckets: [1], dtype: float32,\n"
        "     extra: {image_size: 64}}\n" % tmp_path)
    assert main(["warm", "--config", str(cfg), "--platform", "cpu"]) == 0
    # Engine JSON log lines share stdout; the summary is the last line.
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["executables"] == 1 and out["cold_start_seconds"] > 0


@pytest.mark.parametrize("cmd", ["serve", "warm"])
def test_serving_commands_refuse_a_non_tpu_backend(cmd, tmp_path):
    """Without ``--platform cpu`` a host where JAX found no TPU must not
    serve: exit non-zero at start, naming the flag — before any model is
    built (the config names one that does not exist)."""
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text("models:\n  - {name: no_such_model}\n")
    with pytest.raises(SystemExit) as e:
        main([cmd, "--config", str(cfg)])
    assert e.value.code not in (0, None)
    assert "--platform cpu" in str(e.value.code)
    assert "'cpu' backend" in str(e.value.code)


@pytest.mark.parametrize("platform", [None, "cpu"],
                         ids=["no-platform-off-a-tpu-exits",
                              "cpu-by-name-passes"])
def test_require_tpu(platform):
    """The gate itself, in this process (which holds the CPU backend): no
    ``--platform`` off a TPU exits naming the flag and what asked; the CPU
    asked for by name passes and gets what JAX reports."""
    from pytorch_zappa_serverless_tpu.utils.device import (device_info,
                                                           require_tpu)

    if platform is None:
        with pytest.raises(SystemExit) as e:
            require_tpu(None, "tpuserve serve")
        assert str(e.value.code).startswith("tpuserve serve needs a TPU")
        assert "--platform cpu" in str(e.value.code)
    else:
        info = require_tpu(platform, "tpuserve serve")
        assert info == device_info() and info["platform"] == "cpu"


def test_render_deploy_emits_mounted_config(tmp_path):
    """The Dockerfile CMD mounts /etc/tpuserve/config.yaml — render must emit
    it, self-consistently loadable (VERDICT r1 item 9)."""
    from pytorch_zappa_serverless_tpu.config import ModelConfig, load_config

    cfg = ServeConfig(profile="prod", port=8080, models=[
        ModelConfig(name="resnet18", batch_buckets=(1, 4))])
    summary = render_deploy(cfg, target="cloudrun", out_dir=tmp_path)
    assert "config.yaml" in summary["files"]
    loaded = load_config(tmp_path / "config.yaml")
    assert loaded.profile == "prod" and loaded.port == 8080
    assert loaded.models[0].name == "resnet18"
    assert loaded.models[0].batch_buckets == (1, 4)


def test_config_dump_round_trip(tmp_path):
    from pytorch_zappa_serverless_tpu.config import (
        ModelConfig, dump_config, load_config)

    cfg = ServeConfig(profile="x", port=9999, mesh={"data": 2, "model": 4},
                      models=[ModelConfig(name="bert_base", seq_buckets=(64, 128),
                                          extra={"num_labels": 3})])
    path = tmp_path / "cfg.yaml"
    path.write_text(dump_config(cfg))
    loaded = load_config(path)
    assert loaded == cfg


def test_stage_assets_round_trip(tmp_path):
    """stage → staged config.yaml → serving from the native params gives the
    same predictions as the original builder (the asset pipeline's whole
    correctness claim)."""
    import numpy as np
    import jax

    from pytorch_zappa_serverless_tpu.cli import main as cli_main
    from pytorch_zappa_serverless_tpu.config import load_config
    from pytorch_zappa_serverless_tpu.deploy.stage import stage_assets
    from pytorch_zappa_serverless_tpu.utils.registry import get_model_builder
    from pytorch_zappa_serverless_tpu import models as _zoo  # noqa: F401

    labels = tmp_path / "labels.json"
    labels.write_text(json.dumps([f"l{i}" for i in range(1000)]))
    cfg_path = tmp_path / "cfg.yaml"
    cfg_path.write_text(
        "models:\n"
        "  - {name: resnet18, batch_buckets: [1], dtype: float32,\n"
        "     extra: {image_size: 64, labels: '%s'}}\n" % labels)
    out = tmp_path / "staged"
    assert cli_main(["stage", "--config", str(cfg_path), "--out", str(out),
                     "--mount-root", str(out / "assets")]) == 0

    staged_cfg = load_config(out / "config.yaml")
    mc = staged_cfg.models[0]
    assert mc.checkpoint.endswith(".tpu.safetensors")
    assert mc.extra["labels"].endswith("labels.json")

    # Same RNG seed → staging the random-init params must reproduce the
    # original servable exactly when reloaded through the native path.
    orig = get_model_builder("resnet18")(load_config(cfg_path).models[0])
    staged = get_model_builder("resnet18")(mc)
    img = np.random.default_rng(0).integers(0, 256, (1, 64, 64, 3), np.uint8)
    a = jax.jit(orig.apply_fn)(orig.params, {"image": img})
    b = jax.jit(staged.apply_fn)(staged.params, {"image": img})
    np.testing.assert_array_equal(np.asarray(a["topk_packed"]),
                                  np.asarray(b["topk_packed"]))
    # Staged labels file is live: postprocess resolves through it.
    post = staged.postprocess(jax.tree.map(np.asarray, b), 0)
    assert post["top_k"][0]["label"].startswith("l")


def test_stage_quantized_lane_round_trip(tmp_path):
    """Staging a params_dtype lane saves the PRE-quantization tree and the
    staged config re-quantizes at boot — staging the quantized tree would
    feed the builder's rewrite its own output (gpt2's q/k/v fusion
    crashes on kernel_q nodes)."""
    import numpy as np
    import jax

    from pytorch_zappa_serverless_tpu.config import load_config
    from pytorch_zappa_serverless_tpu.deploy.stage import stage_assets
    from pytorch_zappa_serverless_tpu.utils.registry import get_model_builder
    from pytorch_zappa_serverless_tpu import models as _zoo  # noqa: F401

    cfg_path = tmp_path / "cfg.yaml"
    cfg_path.write_text(
        "models:\n"
        "  - {name: gpt2, batch_buckets: [1], seq_buckets: [16],\n"
        "     dtype: bfloat16,\n"
        "     extra: {max_new_tokens: 4, params_dtype: int8,\n"
        "             quantize_min_size: 1024,\n"
        "             arch: {vocab_size: 512, d_model: 128, layers: 2,\n"
        "                    heads: 2, ffn_dim: 256, max_positions: 64,\n"
        "                    eos_id: 511}}}\n")
    out = tmp_path / "staged"
    stage_assets(load_config(cfg_path), out_dir=out,
                 mount_root=str(out / "assets"))

    staged_cfg = load_config(out / "config.yaml")
    mc = staged_cfg.models[0]
    assert mc.extra["params_dtype"] == "int8"  # the lane survives staging
    # The staged TREE is raw (no quantized nodes)...
    from pytorch_zappa_serverless_tpu.engine import weights as W

    flat = W.flatten_tree(W.load_native(mc.checkpoint))
    assert not any(k.endswith("kernel_q") for k in flat)
    # ...and booting from it quantizes + serves: same tokens as building
    # the int8 lane directly from the same seed.
    staged = get_model_builder("gpt2")(mc)
    assert staged.params["layer0"]["qkv"]["kernel_q"].dtype == np.int8
    orig = get_model_builder("gpt2")(load_config(cfg_path).models[0])
    inputs = {"input_ids": np.asarray([[5, 6, 7, 0, 0, 0, 0, 0]], np.int32),
              "length": np.asarray([3], np.int32),
              "temperature": np.zeros((1,), np.float32),
              "seed": np.zeros((1,), np.int32),
              "top_k": np.zeros((1,), np.int32),
              "top_p": np.ones((1,), np.float32),
              "repetition_penalty": np.ones((1,), np.float32)}
    a = np.asarray(jax.jit(orig.apply_fn)(orig.params, inputs)["tokens"])
    b = np.asarray(jax.jit(staged.apply_fn)(staged.params, inputs)["tokens"])
    np.testing.assert_array_equal(a, b)


def test_tail_cli(tmp_path, capsys):
    from pytorch_zappa_serverless_tpu.cli import main as cli_main

    logf = tmp_path / "server.log"
    logf.write_text(
        '{"ts": 1700000000.0, "level": "info", "logger": "engine", "msg": "model ready", "model": "resnet18"}\n'
        '{"ts": 1700000001.0, "level": "error", "logger": "serving", "msg": "boom"}\n'
        "not-json\n")
    assert cli_main(["tail", str(logf)]) == 0
    out = capsys.readouterr().out
    assert "model ready" in out and 'model="resnet18"' in out
    assert "ERROR" in out and "boom" in out
    assert "not-json" in out

    assert cli_main(["tail", str(logf), "--level", "error"]) == 0
    out = capsys.readouterr().out
    assert "boom" in out and "model ready" not in out

    assert cli_main(["tail", str(logf), "--grep", "resnet18"]) == 0
    out = capsys.readouterr().out
    assert "model ready" in out and "boom" not in out
