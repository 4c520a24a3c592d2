"""Pin the assigned architecture configs to the assignment sheet, and unit-
test the launcher plumbing (shape registry, cache spec rules, HLO collective
parser, wire-byte accounting) without touching jax device state."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke
from repro.core.gossip import gossip_wire_bytes
from repro.launch import shapes as SH
from repro.analysis.hlo import shape_bytes as _shape_bytes, parse_collectives

EXPECTED = {
    # arch: (layers, d_model, heads, kv, d_ff, vocab)
    "rwkv6-7b": (32, 4096, None, None, 14336, 65536),
    "minicpm3-4b": (62, 2560, 40, 40, 6400, 73448),
    "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
    "tinyllama-1.1b": (22, 2048, 32, 4, 5632, 32000),
    "h2o-danube-3-4b": (24, 3840, 32, 8, 10240, 32000),
    "chatglm3-6b": (28, 4096, 32, 2, 13696, 65024),
    "grok-1-314b": (64, 6144, 48, 8, 32768, 131072),
    "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
    "paligemma-3b": (18, 2048, 8, 1, 16384, 257216),
    "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
}


@pytest.mark.parametrize("arch", ARCHS)
def test_config_matches_assignment(arch):
    cfg = get_config(arch)
    L, d, h, kv, ff, v = EXPECTED[arch]
    assert cfg.n_layers == L and cfg.d_model == d
    assert cfg.d_ff == ff and cfg.vocab == v
    if h is not None:
        assert cfg.n_heads == h and cfg.n_kv_heads == kv


def test_assignment_extras():
    assert get_config("grok-1-314b").n_experts == 8
    assert get_config("grok-1-314b").top_k == 2
    assert get_config("arctic-480b").n_experts == 128
    assert get_config("arctic-480b").dense_residual
    assert get_config("minicpm3-4b").mla
    assert get_config("h2o-danube-3-4b").window == 4096
    assert get_config("chatglm3-6b").rotary_frac == 0.5
    assert get_config("zamba2-7b").ssm_state == 64
    assert get_config("paligemma-3b").n_prefix == 256
    assert get_config("seamless-m4t-medium").n_enc_layers == 12


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_is_reduced(arch):
    cfg = get_smoke(arch)
    assert cfg.n_layers <= 8
    assert cfg.d_model <= 512
    assert cfg.n_experts <= 4


def test_shape_registry():
    assert SH.SHAPES["train_4k"].seq_len == 4096
    assert SH.SHAPES["train_4k"].global_batch == 256
    assert SH.SHAPES["prefill_32k"].global_batch == 32
    assert SH.SHAPES["decode_32k"].global_batch == 128
    assert SH.SHAPES["long_500k"].seq_len == 524288
    # long_500k applicability per DESIGN.md
    runs = [a for a in ARCHS if SH.shape_applicable(a, "long_500k")]
    assert sorted(runs) == sorted(["rwkv6-7b", "h2o-danube-3-4b",
                                   "zamba2-7b"])
    for a in ARCHS:
        assert SH.shape_applicable(a, "train_4k")


def test_train_batch_specs_shapes():
    cfg = get_config("tinyllama-1.1b")
    batch, specs = SH.train_batch_specs(cfg, SH.SHAPES["train_4k"], 16,
                                        ("data",))
    assert batch["tokens"].shape == (16, 16, 4096)
    cfg = get_config("paligemma-3b")
    batch, specs = SH.train_batch_specs(cfg, SH.SHAPES["train_4k"], 16,
                                        ("data",))
    assert batch["tokens"].shape == (16, 16, 4096 - 256)
    assert batch["patches"].shape == (16, 16, 256, 1152)
    cfg = get_config("seamless-m4t-medium")
    batch, specs = SH.train_batch_specs(cfg, SH.SHAPES["train_4k"], 32,
                                        ("pod", "data"))
    assert batch["frames"].shape == (32, 8, 2048, 1024)


def test_cache_pspec_rules():
    from jax.sharding import PartitionSpec as P
    cache = {
        "k": jax.ShapeDtypeStruct((22, 128, 32768, 4, 64), jnp.bfloat16),
        "v": jax.ShapeDtypeStruct((22, 128, 32768, 4, 64), jnp.bfloat16),
        "positions": jax.ShapeDtypeStruct((22, 128, 4096), jnp.int32),
        "S": jax.ShapeDtypeStruct((32, 1, 64, 64, 64), jnp.float32),
        "conv": jax.ShapeDtypeStruct((81, 128, 3, 7296), jnp.float32),
    }
    specs = SH.cache_pspecs(cache, ("data",), 16)
    assert specs["k"] == P(None, "data", "model", None, None)
    assert specs["positions"] == P(None, "data", None)
    assert specs["S"] == P(None, None, "model", None, None)  # B=1
    assert specs["conv"] == P(None, "data", None, "model")


def test_hlo_shape_bytes_and_collective_parser():
    # dryrun re-exports the canonical analysis passes (back-compat surface)
    from repro.launch import dryrun
    assert dryrun.parse_collectives is parse_collectives
    assert dryrun._shape_bytes is _shape_bytes

    assert _shape_bytes("bf16[16,2048]{1,0}") == 16 * 2048 * 2
    assert _shape_bytes("(f32[8,4]{1,0}, s32[8]{0})") == 8 * 4 * 4 + 8 * 4
    hlo = """
      %ag = f32[16,1024]{1,0} all-gather(f32[1,1024] %p), dims={0}
      %ar.1 = bf16[512]{0} all-reduce(bf16[512] %x), to_apply=%add
      %cp = f32[4,4]{1,0} collective-permute(f32[4,4] %y), pairs={{0,1}}
      %ag2 = f32[8]{0} all-gather-start(f32[1] %q)
      %agd = f32[8]{0} all-gather-done(f32[8] %ag2)
      %normal = f32[2]{0} add(f32[2] %a, f32[2] %b)
    """
    out = parse_collectives(hlo)
    assert out["all-gather"]["count"] == 2          # ag + ag-start, not -done
    assert out["all-gather"]["bytes"] == 16 * 1024 * 4 + 8 * 4
    assert out["all-reduce"]["bytes"] == 512 * 2
    assert out["collective-permute"]["count"] == 1


def test_gossip_wire_accounting():
    d, n = 1_000_000, 16
    dense = gossip_wire_bytes("dense", n, d)
    ring = gossip_wire_bytes("ring", n, d)
    packed = gossip_wire_bytes("packed", n, d, frac=0.05)
    assert dense == n * d * 4
    assert ring == 2 * d * 4                         # n-independent for n>2
    # n=2 ring has one neighbor: a single shift crosses the wire
    assert gossip_wire_bytes("ring", 2, d) == d * 4
    # packed follows the executor's block format, ~n*frac*d*8 up to padding
    assert packed == pytest.approx(n * 0.05 * d * 8, rel=0.01)
    # at rho=0.05, n=16: packed (n*rho*2x) beats ring (2x dense payload)
    assert packed < ring < dense


def test_packed_wire_bytes_match_executor_payload():
    """gossip_wire_bytes('packed') must equal the bytes of the actual
    (values, int32 indices) payload make_packed_mixer all-gathers: k_b =
    max(round(frac*PACK_BLOCK), 1) pairs per PACK_BLOCK-padded window per
    agent -- not max(frac*d, 1) pairs (which under-reported for small or
    badly padded buffers)."""
    from repro.core.gossip import PACK_BLOCK

    n = 4
    for d, frac in ((10, 0.05), (123, 0.25), (PACK_BLOCK, 0.05),
                    (5000, 0.1), (1_000_000, 0.05)):
        # the executor's pack stage, verbatim: pad to windows, top-k each
        flat = jnp.arange(1.0, d + 1.0, dtype=jnp.float32)
        rows = jnp.pad(flat, (0, (-d) % PACK_BLOCK)).reshape(-1, PACK_BLOCK)
        k_b = max(int(round(frac * PACK_BLOCK)), 1)
        vals, idx = jax.lax.top_k(jnp.abs(rows), k_b)
        payload = n * (vals.size * 4 + idx.size * 4)  # f32 vals + int32 idx
        assert gossip_wire_bytes("packed", n, d, frac=frac) == payload
    # a 10-element buffer still ships one full window's k_b pairs
    assert gossip_wire_bytes("packed", n, 10, frac=0.05) == \
        n * max(round(0.05 * PACK_BLOCK), 1) * 8


def test_decode_window_rules():
    assert SH.decode_window(get_config("zamba2-7b"),
                            SH.SHAPES["long_500k"]) == 4096
    assert SH.decode_window(get_config("zamba2-7b"),
                            SH.SHAPES["decode_32k"]) == "cfg"


# ---------------------------------------------------------------------------
# train.py: the depth cut and the device line
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["minicpm3-4b", "tinyllama-1.1b"])
def test_train_n_layers_keeps_published_widths(arch):
    import dataclasses
    from repro.launch.train import model_config
    full = model_config(arch)
    cut = model_config(arch, n_layers=1)
    assert cut.n_layers == 1
    assert dataclasses.replace(cut, n_layers=full.n_layers) == full
    assert full == dataclasses.replace(get_config(arch), remat=False)


def test_train_smoke_runs_unchanged_by_depth_option(tmp_path, capsys):
    import json
    from repro.launch.train import main, model_config
    assert model_config("tinyllama-1.1b", smoke=True) == \
        model_config("tinyllama-1.1b", smoke=True,
                     n_layers=get_smoke("tinyllama-1.1b").n_layers)
    base = ["--arch", "tinyllama-1.1b", "--smoke", "--steps", "2",
            "--batch", "1", "--seq", "16", "--agents", "2", "--log-every",
            "1"]
    smoke_layers = str(get_smoke("tinyllama-1.1b").n_layers)
    hist, codes = [], []
    for extra in ([], ["--n-layers", smoke_layers]):
        out = tmp_path / f"h{len(hist)}.json"
        codes.append(main(base + extra + ["--out", str(out)]))
        hist.append([h["loss"] for h in json.loads(out.read_text())])
    assert codes[0] == codes[1]
    assert len(hist[0]) == 2 and np.isfinite(hist[0]).all()
    assert hist[0] == hist[1]
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("[model]")][0]
    dev = jax.devices()[0]
    assert f"on {dev.platform} {dev.device_kind} x{len(jax.devices())}" \
        in line
