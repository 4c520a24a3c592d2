"""A micro cell for the CPU tests of the check that decides ``correct``.

A minicpm3-shaped configuration with every width cut (which only a test
may do) under the two traffic mixes of the chip cells at a few rows, a
BENCHMARK.json that names them, and the faults a training cell can have,
planted under the timed path.  The limits are the micro cells' own, set
from CPU readings on ``SEED``: sound runs read at most (loss, grad, v, m_v,
dx) 9.4e-5, 4.4e-3, 4.4e-3, 1.9e-2, 1.8e-2 (gc) and 7.6e-5, 1.4e-4,
1.6e-4, 1.6e-4, 4.8e-4 (dp) and 4.5e-4, 3.4e-3, 3.4e-3, 3.3e-2, 0.12
(ring4, on four CPU devices) and 3.5e-5, 4.3e-3, 4.3e-3, 1.7e-2, 3.7e-3
(graph: four agents on a listed graph, f32 planes) over three seeds; the
float8 control reads loss 3.9e-4 and grad 3.7e-2 (gc), loss 3.1e-4 and
m_v 1.1e-3 (dp), loss 1.8e-3 and grad 2.5e-2 (ring4), grad 3.2e-2 to
3.7e-2 (graph).  The chip cells' limits are in ``limits/`` and
``PERF.md``.
"""

import dataclasses
import json

import numpy as np

from chipbench import run

MICRO = {
    "arch": "minicpm3-4b", "source": "test", "hidden_size": 64,
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "intermediate_size": 128, "hidden_act": "silu", "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "tie_word_embeddings": True, "rope_theta": 10000.0,
    "num_hidden_layers": 1, "vocab_size": 256}
MICRO["reduced"] = [k for k in MICRO if k not in
                    ("arch", "source", "hidden_act", "tie_word_embeddings",
                     "rope_theta")]
MICRO["program_fields"] = {
    "d_model": "hidden_size", "n_heads": "num_attention_heads",
    "n_kv_heads": "num_key_value_heads", "d_ff": "intermediate_size",
    "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_dim": "qk_nope_head_dim", "qk_rope_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim", "n_layers": "num_hidden_layers",
    "vocab": "vocab_size", "tie_embeddings": "tie_word_embeddings"}
GLM = {
    "arch": "chatglm3-6b", "source": "test", "hidden_size": 64,
    "num_attention_heads": 4, "multi_query_group_num": 2, "kv_channels": 16,
    "ffn_hidden_size": 128, "hidden_act": "swiglu", "add_qkv_bias": True,
    "rotary_fraction": 0.5, "tie_word_embeddings": False, "num_layers": 1,
    "padded_vocab_size": 256}
GLM["reduced"] = [k for k in GLM if k not in
                  ("arch", "source", "hidden_act", "add_qkv_bias",
                   "rotary_fraction", "tie_word_embeddings")]
GLM["program_fields"] = {
    "d_model": "hidden_size", "n_heads": "num_attention_heads",
    "n_kv_heads": "multi_query_group_num", "hd": "kv_channels",
    "d_ff": "ffn_hidden_size", "qkv_bias": "add_qkv_bias",
    "rotary_frac": "rotary_fraction", "n_layers": "num_layers",
    "vocab": "padded_vocab_size", "tie_embeddings": "tie_word_embeddings"}
GLM["program_setters"] = {"hd": "head_dim"}
CONFIGS = {"gc": ("micro", MICRO), "dp": ("micro", MICRO),
           "graph": ("micro", MICRO), "ring4": ("glm-micro", GLM)}
TRAFFIC = {
    "gc": {"algo": "porter-gc", "batch": 2, "seq": 16, "dp": None},
    "dp": {"algo": "porter-dp", "batch": 4, "seq": 8,
           "dp": {"epsilon": 0.1, "delta": 0.001, "horizon": 50,
                  "local_samples": 4096}},
    # a graph that the traffic lists edge by edge, and exact f32 planes
    "graph": {"algo": "porter-gc", "agents": 4, "batch": 2, "seq": 16,
              "dp": None, "topology": "listed",
              "edges": [[0, 1], [1, 2], [1, 3], [2, 3]],
              "plane_dtype": "f32"},
    "ring4": {"algo": "porter-gc", "agents": 4, "chips": 4, "batch": 2,
              "seq": 16, "dp": None, "gossip_mode": "ring",
              "wire": "packed_bits", "eta": 0.001}}
COMMON = {"agents": 2, "chips": 1, "topology": "ring", "gossip_mode": "dense",
          "wire": "dense", "compressor": "top_k", "frac": 0.05,
          "plane_dtype": "bf16", "chunk": 2, "eta": 0.03, "tau": 1.0}
# the micro cells' limits: above the sound runs' readings on these seeds,
# below the control's and the faults'
LIMITS = {"gc": {"loss": 2e-4, "grad": 1.2e-2, "v": 1.2e-2, "m_v": 8e-2,
                 "dx": 6e-2},
          "dp": {"loss": 1e-4, "grad": 1e-3, "v": 1e-3, "m_v": 3e-4,
                 "dx": 1.2e-3},
          "graph": {"loss": 2e-4, "grad": 1.2e-2, "v": 1.2e-2, "m_v": 6e-2,
                    "dx": 1.2e-2},
          "ring4": {"loss": 9e-4, "grad": 1e-2, "v": 1e-2, "m_v": 0.1,
                    "dx": 0.15}}
SEED = 3_000_000_019


def write_bench(root):
    """BENCHMARK.json and the micro cells' files under ``root``."""
    for sub in ("configs", "traffic", "limits"):
        (root / "chipbench" / sub).mkdir(parents=True)
    for name, config in CONFIGS.values():
        (root / "chipbench" / "configs" / f"{name}.json").write_text(
            json.dumps(config))
    workloads = []
    for kind, t in TRAFFIC.items():
        (root / "chipbench" / "traffic" / f"micro-{kind}.json").write_text(
            json.dumps({**COMMON, **t}))
        (root / "chipbench" / "limits" / f"micro-{kind}.json").write_text(
            json.dumps(LIMITS[kind]))
        workloads.append({"name": f"micro-{kind}",
                          "config": CONFIGS[kind][0],
                          "traffic": f"micro-{kind}",
                          "chips": {**COMMON, **t}["chips"], "why": "test"})
    path = root / "BENCHMARK.json"
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    path.write_text(json.dumps({**bench, "workloads": workloads}))
    return path


def run_cell(bench, kind, **build_kw):
    return run.run(["--workload", f"micro-{kind}", "--seed", str(SEED),
                    "--seconds", "0.5"], require_tpu=False,
                   build_kw=build_kw, bench_path=bench)


def frozen(step):
    """A step that returns its state unchanged."""
    def frozen(state, batch, key):
        return state, step(state, batch, key)[1]
    return frozen


def half_batch(step):
    """Half of each agent's batch left out, the mean over the rest."""
    def half(state, batch, key):
        toks = batch["tokens"]
        return step(state, {"tokens": toks[:, : toks.shape[1] // 2]}, key)
    return half


def no_exchange(kind: str = "gc"):
    """The gossip mix left out: W = I, with the cell's alpha (and so the
    same consensus step)."""
    from chipbench import cell as C
    traffic = {**COMMON, **TRAFFIC[kind]}
    return dataclasses.replace(C.edges_topology(traffic),
                               w=np.eye(traffic["agents"]))


FAULTS = {"frozen": lambda kind="gc": {"step_wrap": frozen},
          "half_batch": lambda kind="gc": {"step_wrap": half_batch},
          "no_exchange": lambda kind="gc": {"topology": no_exchange(kind)}}


def agent_per_chip_readings(root, seeds=(SEED,)) -> dict:
    """Sound runs, planted faults and the control of the micro ring4 cell;
    needs a process with four devices (XLA_FLAGS'
    --xla_force_host_platform_device_count=4).  The exchange between chips
    is left out by shipping zeros in place of every ppermute's payload."""
    import jax
    import jax.numpy as jnp
    from unittest import mock

    from chipbench import control

    bench = write_bench(root)
    out = {"sound": [], "faults": {}}
    for seed in seeds:
        res = run.run(["--workload", "micro-ring4", "--seed", str(seed),
                       "--seconds", "0.5"], require_tpu=False,
                      bench_path=bench)
        out["sound"].append({k: v["value"] for k, v in res["check"].items()})
    for name, kw in (("frozen", {"step_wrap": frozen}),
                     ("half_batch", {"step_wrap": half_batch})):
        res = run_cell(bench, "ring4", **kw)
        out["faults"][name] = {k: v["value"] for k, v in res["check"].items()}
    silent = lambda x, axis_name, perm: jnp.zeros_like(x)
    with mock.patch.object(jax.lax, "ppermute", silent):
        res = run_cell(bench, "ring4")
    out["faults"]["no_exchange"] = {k: v["value"]
                                    for k, v in res["check"].items()}
    out["control"] = control.readings("micro-ring4", [SEED],
                                      bench_path=bench,
                                      variants=("control",))[0]["control"]
    return out


if __name__ == "__main__":
    import sys
    import tempfile
    from pathlib import Path
    seeds = [int(s) for s in sys.argv[2:]] or [SEED]
    print(json.dumps(agent_per_chip_readings(Path(tempfile.mkdtemp()),
                                             seeds)))
