"""Helpers of the benchmark's own tests: small copies of the cells, laid
out in a directory the harness has never seen, so that the tests also show
that it finds every file by name."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
for _p in (REPO, REPO / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

# small stand-ins of the two configurations and their traffic: the same
# builders and references, at sizes a CPU test holds. The small LM runs in
# float32, so that the program and the reference agree to rounding on the
# CPU: in bfloat16 at these widths a near-tie in the quantizer moves its
# gradients by several percent.
TINY_LM_ARCH = dict(num_layers=2, d_model=128, vocab_size=512, num_heads=4,
                    num_kv_heads=2, head_dim=32, d_ff=256, cut_periods=1,
                    sliding_window=48, attn_q_chunk=32, dtype="float32",
                    param_dtype="float32")
TINY_CFG = {
    "femnist_cnn": {},
    "starcoder2_3b_d6": {"arch": TINY_LM_ARCH,
                         "pq": {"num_subvectors": 16, "num_clusters": 4,
                                "kmeans_iters": 2}},
}
TINY_MIX = {
    "cohort10": {"num_clients": 8, "cohort": 2, "client_batch": 4,
                 "batches_per_client": 4},
    "seq2048": {"num_clients": 8, "cohort": 2, "seq": 64,
                "batches_per_client": 4},
    # two clients on each of four devices
    "mesh4_cohort40": {"num_clients": 16, "cohort": 8, "client_batch": 4,
                       "batches_per_client": 4},
}
MIX = {"femnist_cnn": "cohort10", "starcoder2_3b_d6": "seq2048"}


def _merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) else v
    return out


def make_root(tmp: Path, config: str, *, mix: str = None, chips: int = 1,
              builder_src: str = None, limits: dict = None,
              name: str = "tiny") -> tuple:
    """A checkout-like directory holding one small cell of ``config`` under
    a small copy of the traffic mix ``mix`` (by default the configuration's
    one-chip mix). Returns (root, workload name)."""
    mix_name = mix or MIX[config]
    bench = tmp / "benchdir"
    for sub in ("configs", "traffic", "reference", "limits", "metrics"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    for f in (BENCH / "metrics").glob("*.py"):
        shutil.copy(f, bench / "metrics" / f.name)
    cfg = _merge(json.loads((BENCH / "configs" / f"{config}.json")
                            .read_text()), TINY_CFG[config])
    mix = _merge(json.loads((BENCH / "traffic" / f"{mix_name}.json")
                            .read_text()), TINY_MIX[mix_name])
    cname = f"{name}_{config}"
    (bench / "configs" / f"{cname}.json").write_text(json.dumps(cfg))
    (bench / "configs" / f"{cname}.py").write_text(
        builder_src if builder_src is not None else
        (BENCH / "configs" / f"{config}.py").read_text())
    shutil.copy(BENCH / "reference" / f"{config}.py",
                bench / "reference" / f"{cname}.py")
    (bench / "traffic" / f"{cname}_mix.json").write_text(json.dumps(mix))
    workload = f"{cname}.small"
    if limits is None:
        limits = load_json(f"limits/{config}.{mix_name}.json")
    (bench / "limits" / f"{workload}.json").write_text(json.dumps(limits))
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    manifest["configs"] = [{"name": cname, "source": "test",
                            "file": f"benchdir/configs/{cname}.json",
                            "reduced": [], "why": "test"}]
    manifest["workloads"] = [{"name": workload, "config": cname,
                              "traffic": f"{cname}_mix", "chips": chips,
                              "why": "test"}]
    for m in manifest["per_layer"] + manifest["end_to_end"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    return tmp, workload


def load_json(rel: str):
    return json.loads((BENCH / rel).read_text())


def load_builder(config: str):
    from bench import harness
    return harness.load_module(BENCH / "configs" / f"{config}.py",
                               f"test_builder_{config}")
