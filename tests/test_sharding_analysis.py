"""Sharding resolver + HLO analysis walker + dry-run integration."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.analysis.flops import analyze_hlo
from repro.analysis.hlo import collective_stats, shape_bytes
from repro.sharding.logical import default_rules, resolve_spec

MESH = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
POD = AbstractMesh((16, 16), ("data", "model"))


def rules(mesh=MESH, **kw):
    return default_rules(mesh, **kw)


def test_resolver_basic_tp():
    r = rules()
    spec = resolve_spec(("embed", "ff"), (4096, 14336), r)
    assert spec == P("data", "model")


def test_resolver_divisibility_fallback():
    r = rules()
    # whisper: 6 heads don't divide 16 -> replicated
    spec = resolve_spec(("batch", None, "q_heads", None), (256, 128, 6, 64), r)
    assert spec[2] is None
    # batch takes the composed ("pod","data") group
    assert spec[0] == ("pod", "data")
    # grok: 8 experts don't divide 16 -> replicated, ff shards instead
    spec = resolve_spec(("experts", "embed", "ff"), (8, 6144, 32768), r)
    assert spec == P(None, "data", "model")
    # olmoe: 64 experts divide 16
    spec = resolve_spec(("experts", "embed", "ff"), (64, 2048, 1024), r)
    assert spec == P("model", "data", "ff" and None) or spec[0] == "model"


def test_resolver_no_axis_reuse():
    r = rules()
    # vocab takes model; heads_merged then cannot reuse model
    spec = resolve_spec(("vocab", "heads_merged"), (151936, 4096), r)
    assert spec[0] == "model" and spec[1] is None


def test_resolver_batch_of_one_replicates():
    r = rules(POD)
    spec = resolve_spec(("batch", "seq_shard", None, None),
                        (1, 524288, 8, 128), r)
    assert spec[0] is None  # 1 % 16 != 0
    assert spec[1] == ("data", "model")  # full 256-way seq shard


def test_serving_rules_drop_fsdp():
    r_train = rules(POD)
    r_serve = rules(POD, serving=True)
    st = resolve_spec(("embed", "heads_merged"), (4096, 4096), r_train)
    ss = resolve_spec(("embed", "heads_merged"), (4096, 4096), r_serve)
    assert st == P("data", "model")
    assert ss == P(None, "model")


# ---------------------------------------------------------------------------
# HLO walker ground truth
# ---------------------------------------------------------------------------


def test_walker_plain_and_scan_ground_truth():
    A = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def plain(x):
        return x @ x

    def scanned(x):
        def body(c, _):
            return c @ x, None

        return jax.lax.scan(body, x, None, length=10)[0]

    want = 2 * 256**3
    c1 = analyze_hlo(jax.jit(plain).lower(A).compile().as_text())
    c2 = analyze_hlo(jax.jit(scanned).lower(A).compile().as_text())
    assert abs(c1.flops - want) / want < 0.02
    assert abs(c2.flops - 10 * want) / (10 * want) < 0.02


def test_shape_bytes():
    assert shape_bytes("bf16[8,128]") == 8 * 128 * 2
    assert shape_bytes("f32[2,2]{1,0} pred[4]") == 16 + 4


def test_collective_parser():
    fake = """
  %ag = f32[16,1024]{1,0} all-gather(%x), replica_groups=...
  %ar.1 = bf16[8,8]{1,0} all-reduce-start(%y), to_apply=%add
  %ar.2 = bf16[8,8]{1,0} all-reduce-done(%ar.1)
"""
    stats = collective_stats(fake)
    assert stats["all-gather"]["count"] == 1
    assert stats["all-gather"]["bytes"] == 16 * 1024 * 4
    assert stats["all-reduce"]["count"] == 1  # start only, not done
    assert stats["total"]["count"] == 2


# ---------------------------------------------------------------------------
# dry-run integration (subprocess: needs its own 512-device jax)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_dryrun_cell_subprocess(tmp_path):
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", "whisper-tiny", "--shape", "decode_32k",
            "--mesh", "pod", "--out", str(tmp_path),
        ],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=900,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    rec = json.load(open(tmp_path / "whisper-tiny__decode_32k__pod.json"))
    assert rec["status"] == "OK"
    assert rec["n_devices"] == 256
    assert rec["roofline"]["bottleneck"] in ("compute", "memory", "collective")
