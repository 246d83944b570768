"""Distribution layer: rules engine, divisibility sanitization (hypothesis),
param-spec validity for every arch × mesh, and a reduced-device dry-run
(8 host devices in a subprocess) proving the full pipeline lowers."""
import json
import os
import pathlib
import subprocess
import sys

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
from jax.sharding import PartitionSpec as PS

from repro.config import MULTI_POD, SHAPES, SINGLE_POD
from repro.configs import assigned_archs, get_config
from repro.distributed.sharding import (make_rules, mesh_axis_size,
                                        param_specs, sanitize_spec)
from repro.models.api import build_model
from repro.models.modules import tree_map_params

ROOT = pathlib.Path(__file__).resolve().parents[1]

MESH_SIZES = {"single": {"data": 16, "model": 16},
              "multi": {"pod": 2, "data": 16, "model": 16}}


@given(dim=st.integers(min_value=1, max_value=10_000),
       k=st.sampled_from([1, 2, 4, 8, 16, 32]))
@settings(max_examples=200, deadline=None)
def test_sanitize_spec_divisibility(dim, k):
    sizes = {"model": k}
    out = sanitize_spec((dim,), PS("model"), sizes)
    if dim % k == 0 and k > 1:
        assert out == PS("model")
    elif k > 1:
        assert out == PS(None)


@pytest.mark.parametrize("arch", assigned_archs())
@pytest.mark.parametrize("mesh_cfg", [SINGLE_POD, MULTI_POD],
                         ids=["single", "multi"])
def test_param_specs_always_divisible(arch, mesh_cfg):
    """Every param leaf's sharding must divide its shape exactly — the
    invariant that made whisper/minicpm/xlstm cells compile."""
    cfg = get_config(arch)
    model = build_model(cfg)
    sizes = dict(zip(mesh_cfg.axes, mesh_cfg.shape))
    for shape_name in ("train_4k", "decode_32k"):
        rules = make_rules(cfg, mesh_cfg, SHAPES[shape_name])
        specs = param_specs(model, rules, sizes)
        decls = model.param_tree()

        def check(path, p):
            spec = _lookup(specs, path)
            for dim, entry in zip(p.shape, list(spec)):
                assert dim % mesh_axis_size(entry, sizes) == 0, \
                    (arch, path, p.shape, spec)
            return None

        tree_map_params(check, decls)


def _lookup(tree, path):
    node = tree
    for part in path.split("/"):
        node = node[part]
    return node


def test_rules_batch_replicated_for_long_500k():
    cfg = get_config("zamba2-7b")
    rules = make_rules(cfg, SINGLE_POD, SHAPES["long_500k"])
    assert rules["batch"] is None                 # batch=1 can't shard
    rules2 = make_rules(cfg, SINGLE_POD, SHAPES["train_4k"])
    assert rules2["batch"] == "data"


def test_rules_moe_expert_placement():
    ds = get_config("deepseek-moe-16b")           # 64 experts % 16 == 0
    r = make_rules(ds, SINGLE_POD, SHAPES["train_4k"])
    assert r["experts"] == "model"
    gk = get_config("grok-1-314b")                # 8 experts % 16 != 0
    r = make_rules(gk, SINGLE_POD, SHAPES["train_4k"])
    assert r["experts"] is None and r["expert_ff"] == "model"


def test_rules_decode_split_kv():
    llama = get_config("llama3-405b")             # kv_heads=8 < 16
    r = make_rules(llama, SINGLE_POD, SHAPES["decode_32k"])
    assert r["kv_heads_act"] is None
    assert r["kv_seq"] == "model"                 # split-KV decode


def test_variants_differ_from_baseline():
    cfg = get_config("qwen3-14b")
    base = make_rules(cfg, SINGLE_POD, SHAPES["train_4k"])
    seqp = make_rules(cfg, SINGLE_POD, SHAPES["train_4k"],
                      variant="seqpar")
    assert seqp["act_seq"] == "model" and base["act_seq"] is None
    z = make_rules(cfg, SINGLE_POD, SHAPES["train_4k"], variant="zero_off")
    assert z["embed"] is None and base["embed"] == "data"


@pytest.mark.slow
def test_dryrun_lite_subprocess():
    """Full dry-run pipeline on 8 fake host devices: lower+compile+roofline
    for a dense train cell and an SSM long-context decode cell."""
    env = dict(os.environ)
    env.update(REPRO_HOST_DEVICES="8", REPRO_MESH_OVERRIDE="4x2;2x2x2",
               PYTHONPATH=str(ROOT / "src"))
    for arch, shape in (("qwen3-1.7b", "decode_32k"),
                        ("xlstm-1.3b", "long_500k")):
        out = ROOT / "artifacts" / "dryrun" / f"{arch}__{shape}__single.json"
        backup = out.read_text() if out.exists() else None
        try:
            r = subprocess.run(
                [sys.executable, "-m", "repro.launch.dryrun", "--arch",
                 arch, "--shape", shape, "--mesh", "single", "--force"],
                capture_output=True, text=True, timeout=600, env=env,
                cwd=str(ROOT))
            assert r.returncode == 0, r.stderr[-2000:]
            rec = json.loads(out.read_text())
            assert rec["status"] == "ok"
            assert rec["roofline"]["flops"] > 0
        finally:
            if backup is not None:
                out.write_text(backup)


def test_serve_fast_profile():
    """§Perf cell C: serving profile drops ZeRO only when weights fit."""
    small = get_config("qwen3-14b")        # 0.9 GB/chip TP shard
    r = make_rules(small, SINGLE_POD, SHAPES["decode_32k"],
                   variant="serve_fast")
    assert r["embed"] is None
    big = get_config("llama3-405b")        # 50 GB/chip TP shard
    r = make_rules(big, SINGLE_POD, SHAPES["decode_32k"],
                   variant="serve_fast")
    assert r["embed"] == "data"            # keeps ZeRO
