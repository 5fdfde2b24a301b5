import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

#: a dense decoder of the gpt2_345m family at a size the CPU holds
TINY_CONF = dict(name="tiny", family="dense", n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=4, d_ff=128, vocab=256,
                 qkv_bias=True, mlp_gelu=True, tie_embeddings=True,
                 rope_theta=10000.0)


def tiny_plan_traffic():
    import harness
    tr = harness.load_traffic("plan-1chip")
    tr.update(seq=32, reps=2, block_steps=2, pool_batches=4)
    return tr


def tiny_search_traffic():
    import harness
    tr = harness.load_traffic("search-pod")
    tr["questions"] = [
        {"chips": 8, "global_batch": 8, "seq": 128,
         "schedules": ["1f1b", "gpipe"], "T": 64, "K": 40},
        {"chips": 4, "global_batch": 8, "seq": 128,
         "schedules": ["1f1b"], "T": 32, "K": 10}]
    return tr


@pytest.fixture
def tiny_bench(monkeypatch):
    """Point the harness at two tiny cells (``tiny.plan-1chip``,
    ``tiny.search-pod``) and leave the compile cache setting alone."""
    import harness
    manifest = harness.load_manifest()
    manifest = dict(manifest)
    manifest["configs"] = [{"name": "tiny", "source": "test",
                            "file": "tiny.json", "reduced": [],
                            "why": "test"}]
    manifest["workloads"] = [
        {"name": "tiny.plan-1chip", "config": "tiny",
         "traffic": "plan-1chip", "chips": 1, "why": "test"},
        {"name": "tiny.search-pod", "config": "tiny",
         "traffic": "search-pod", "chips": 1, "why": "test"}]
    for group in ("end_to_end", "per_layer"):
        manifest[group] = [dict(m) for m in manifest[group]]
        for m in manifest[group]:
            if "workloads" in m:
                m["workloads"] = [w.replace("gpt2_345m.", "tiny.")
                                  .replace("gpt_145b.", "tiny.")
                                  for w in m["workloads"]]
    traffic = {"plan-1chip": tiny_plan_traffic(),
               "search-pod": tiny_search_traffic()}
    monkeypatch.setattr(harness, "load_manifest", lambda *a: manifest)
    monkeypatch.setattr(harness, "load_config",
                        lambda m, name: dict(TINY_CONF))
    monkeypatch.setattr(harness, "load_traffic", lambda name: traffic[name])
    monkeypatch.setattr(harness, "setup_compile_cache", lambda: None)
    monkeypatch.setattr(harness, "peaks_for", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    return manifest
