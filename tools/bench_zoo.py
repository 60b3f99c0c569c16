"""North-star benchmark publisher: the BASELINE.json `configs` rows.

Drives the model zoo through the C++ perf_analyzer over gRPC (native h2
front-end) and genai-perf (streaming TTFT/ITL), then writes the measured
rows into BASELINE.json's ``published`` map and a PERF.md table.

Rows:
- ``simple`` add_sub headline (same config as bench.py);
- ``image_classifier`` (ResNet) batch-swept, shm none/system/tpu;
- ``text_encoder`` (BERT-family) concurrency sweep at fixed seq len;
- ``llm_decode`` gRPC streaming TTFT/ITL via genai-perf;
- large-tensor shm comparison on ``identity_fp32`` (the tpu-shm
  win-or-indict experiment: 4 MiB/request inline vs system vs tpu).

Device placement is confirmed per row from the server statistics extension
(compute_infer deltas) and the jax platform is recorded — a row measured on
the CPU says so instead of masquerading as TPU.

Usage: python tools/bench_zoo.py [--update-baseline] [--perf-md]
"""

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

PA = os.path.join(REPO, "build", "perf_analyzer")


def run_pa(url, model, *, batch=1, concurrency=4, shm="none", shape=None,
           interval_ms=4000, streaming=False):
    cmd = [
        PA, "-m", model, "-u", url, "-i", "grpc",
        "-b", str(batch),
        "--concurrency-range", str(concurrency),
        "--measurement-interval", str(interval_ms),
        "--max-trials", "3",
        "--json-summary",
    ]
    if shm != "none":
        cmd += ["--shared-memory", shm]
    if shape:
        cmd += ["--shape", shape]
    if streaming:
        cmd += ["--streaming"]
    try:
        out = subprocess.run(cmd, capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        return None
    for line in out.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            summary = json.loads(line)
            if "throughput" in summary:
                return summary
    sys.stderr.write(
        f"bench_zoo: {model} shm={shm} b={batch} failed:\n"
        f"{out.stdout[-400:]}\n{out.stderr[-400:]}\n"
    )
    return None


def infer_stats(core, model):
    snap = core.statistics(model)["model_stats"][0]
    return (
        snap["inference_count"],
        snap["inference_stats"]["compute_infer"]["ns"],
    )


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--update-baseline", action="store_true")
    parser.add_argument("--perf-md", action="store_true",
                        help="rewrite the PERF.md published-rows table")
    parser.add_argument("--concurrency", type=int, default=8)
    args = parser.parse_args()

    import jax

    from client_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    platform = jax.devices()[0].platform
    on_device = platform != "cpu"
    print(f"# platform: {platform} (device rows: {on_device})")

    from client_tpu.models.serving import register_zoo_models
    from client_tpu.server.core import ServerCore
    from client_tpu.server.model_repository import ModelRepository
    from client_tpu.testing import InProcessServer

    repo = ModelRepository()
    core = ServerCore(repo)
    # Full-size models only on a real accelerator; a CPU run uses the
    # small variants and says so in the row.
    register_zoo_models(repo, small=not on_device)
    rows = []
    t_start = time.time()

    with InProcessServer(core=core, host="127.0.0.1") as server:
        url = server.grpc_url
        conc = args.concurrency

        # -- headline: simple add_sub ------------------------------------
        s = run_pa(url, "simple", batch=1, concurrency=conc)
        if s:
            rows.append({
                "config": "simple add_sub, gRPC, inline",
                "model": "simple", "platform": "host",
                "concurrency": conc, "batch": 1,
                "infer_per_sec": round(s["throughput"], 1),
                "p99_ms": round(s["p99_us"] / 1000, 2),
            })

        # -- ResNet image classifier: batch sweep x shm modes ------------
        count0, infer_ns0 = infer_stats(core, "image_classifier")
        for shm in ("none", "system", "tpu"):
            for batch in (1, 4, 8):
                s = run_pa(url, "image_classifier", batch=batch,
                           concurrency=conc, shm=shm)
                if not s:
                    continue
                rows.append({
                    "config": f"image_classifier (ResNet"
                              f"{'50/224' if on_device else '18thin/64'}), "
                              f"gRPC, shm={shm}",
                    "model": "image_classifier",
                    "platform": platform,
                    "concurrency": conc, "batch": batch,
                    "infer_per_sec": round(s["throughput"], 1),
                    "images_per_sec": round(s["throughput"] * batch, 1),
                    "p99_ms": round(s["p99_us"] / 1000, 2),
                })
        count, infer_ns = infer_stats(core, "image_classifier")
        rows.append({
            "config": "image_classifier placement check",
            "model": "image_classifier", "platform": platform,
            "served_requests": count - count0,
            "server_compute_infer_ms_total": round(
                (infer_ns - infer_ns0) / 1e6, 1
            ),
            "note": "compute_infer delta over the swept rows (statistics "
                    "extension) confirms execution on the server-side jax "
                    "backend",
        })

        # -- BERT text encoder: concurrency sweep ------------------------
        for c in (1, conc, 4 * conc):
            s = run_pa(url, "text_encoder", batch=1, concurrency=c,
                       shape="INPUT_IDS:64")
            if not s:
                continue
            rows.append({
                "config": f"text_encoder (BERT"
                          f"{'-large' if on_device else '-tiny'}), seq 64, "
                          "gRPC, inline",
                "model": "text_encoder", "platform": platform,
                "concurrency": c, "batch": 1,
                "infer_per_sec": round(s["throughput"], 1),
                "p99_ms": round(s["p99_us"] / 1000, 2),
            })

        # -- large-tensor shm comparison (identity, 4 MiB/request) -------
        for shm in ("none", "system", "tpu"):
            s = run_pa(url, "identity_fp32", batch=1, concurrency=4,
                       shm=shm, shape="INPUT0:1048576")
            if not s:
                continue
            mbps = s["throughput"] * 4.0
            rows.append({
                "config": f"identity_fp32 4MiB/request, gRPC, shm={shm}",
                "model": "identity_fp32", "platform": "host",
                "concurrency": 4, "batch": 1,
                "infer_per_sec": round(s["throughput"], 1),
                "payload_mib_per_sec": round(mbps, 1),
                "p99_ms": round(s["p99_us"] / 1000, 2),
            })

        # -- LLM decode streaming: TTFT / ITL via genai-perf -------------
        import tempfile

        artifact_dir = tempfile.mkdtemp(prefix="bench_zoo_llm_")
        from client_tpu.genai_perf import main as genai_main

        code = genai_main.main([
            "profile", "-m", "llm_decode", "-u", url,
            "--num-prompts", "20",
            "--synthetic-input-tokens-mean", "32",
            "--output-tokens-mean", "16",
            "--concurrency", "2",
            "--measurement-interval", "6000",
            "--max-trials", "2",
            "--stability-percentage", "75",
            "--artifact-dir", artifact_dir,
        ])
        metrics_path = os.path.join(artifact_dir, "llm_metrics.json")
        if code == 0 and os.path.exists(metrics_path):
            with open(metrics_path) as f:
                m = json.load(f)

            def stat(name, field="avg"):
                entry = m.get(name) or {}
                return entry.get(field)

            rows.append({
                "config": "llm_decode (llama tiny), gRPC streaming, "
                          "genai-perf",
                "model": "llm_decode", "platform": platform,
                "concurrency": 2,
                "ttft_ms": round((stat("time_to_first_token") or 0) / 1e6, 2),
                "itl_ms": round((stat("inter_token_latency") or 0) / 1e6, 2),
                "output_tok_per_sec": round(
                    m.get("output_token_throughput_per_s") or 0, 1
                ),
                "req_per_sec": round(
                    m.get("request_throughput_per_s") or 0, 2
                ),
            })

    result = {
        "measured_at_platform": platform,
        "elapsed_s": round(time.time() - t_start, 1),
        "rows": rows,
    }
    print(json.dumps(result, indent=2))

    if args.update_baseline:
        baseline_path = os.path.join(REPO, "BASELINE.json")
        with open(baseline_path) as f:
            baseline = json.load(f)
        published = baseline.setdefault("published", {})
        published[platform] = result
        with open(baseline_path, "w") as f:
            json.dump(baseline, f, indent=2)
        print(f"# published -> BASELINE.json under key '{platform}'")

    if args.perf_md:
        lines = [
            "",
            f"## Published zoo benchmarks ({platform}, "
            f"{time.strftime('%Y-%m-%d')})",
            "",
            "| config | conc | batch | infer/s | p99 ms | extra |",
            "|---|---|---|---|---|---|",
        ]
        for r in rows:
            extra = []
            for k in ("images_per_sec", "payload_mib_per_sec", "ttft_ms",
                      "itl_ms", "output_tok_per_sec",
                      "server_compute_infer_ms_total"):
                if k in r:
                    extra.append(f"{k}={r[k]}")
            lines.append(
                f"| {r['config']} | {r.get('concurrency', '')} | "
                f"{r.get('batch', '')} | {r.get('infer_per_sec', '')} | "
                f"{r.get('p99_ms', '')} | {'; '.join(extra)} |"
            )
        with open(os.path.join(REPO, "PERF.md"), "a") as f:
            f.write("\n".join(lines) + "\n")
        print("# appended table -> PERF.md")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
