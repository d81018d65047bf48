"""End-to-end smoke of ``bench.py --mode serve`` on a forced 4-device
CPU backend: the report must carry the replica scaling curve and the
pipeline on/off speedup with the per-replica zero-recompile verdicts —
so the serving BENCH schema can't silently rot while CI only exercises
the in-process pieces."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = [pytest.mark.serve, pytest.mark.slow]


def test_bench_serve_reports_scaling_and_pipeline_fields():
    env = os.environ.copy()
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "BENCH_FORCE_CPU": "1",
        # Small drives: this asserts SCHEMA, not throughput. The compile
        # cache stays off — the bench child both writes and re-reads
        # entries in one process, the exact pattern DESIGN.md 6c bans.
        "BENCH_SERVE_REQUESTS": "64",
        "BENCH_SERVE_POOL_REQUESTS": "64",
        "BENCH_SERVE_FUSED_REQUESTS": "48",
        "BENCH_SERVE_CONCURRENCY": "8",
        "BENCH_FLEET_SECONDS": "0.6",
        "BENCH_FLEET_PAIRS": "2",
        "BENCH_FLEET_REQUESTS": "24",
        "BENCH_ECONOMICS_SECONDS": "0.6",
        "BENCH_ECONOMICS_REQUESTS": "48",
        "JAX_COMPILATION_CACHE_DIR": "",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--mode", "serve"],
        capture_output=True, text=True, timeout=540, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])

    assert report["metric"] == "mnist_serve_requests_per_sec"
    assert report.get("error") is None
    assert report["value"] > 0
    assert report["n_chips"] == 4

    # The replica scaling curve: one point per replica count, each with
    # a positive rate and a per-point zero-recompile verdict.
    scaling = report["replica_scaling"]
    assert [pt["replicas"] for pt in scaling] == [1, 2, 4]
    for pt in scaling:
        assert pt["requests_per_sec"] > 0
        assert pt["zero_steady_state_recompiles"] is True

    # Pipeline on/off speedup at the full pool, and the fleet-wide
    # recompile verdict.
    assert isinstance(report["pipeline_speedup"], (int, float))
    assert report["pipeline_speedup"] > 0
    assert report["zero_steady_state_recompiles"] is True
    assert report["zero_steady_state_recompiles_per_replica"] is True

    # Per-replica compile rows really are per replica in the stats blob.
    programs = report["compile_stats"]["programs"]
    assert any(name.endswith("@r3") for name in programs)
    assert any(name.endswith("@r0") for name in programs)

    # The sharded block: one entry per mode (tensor x vit, expert x
    # moe_mlp) with the ABBA-paired vs-replicated ratio, the
    # mesh-scaling curve at fixed chip count, and the per bucket x mode
    # zero-recompile verdict. This CPU run is a forced-multi-device
    # world with the Eigen isolation, so it must carry the
    # BENCH_r05-style fallback caveat.
    sharded = report["sharded"]
    assert report["cpu_serve_devices_isolated"] is True
    assert "CPU fallback" in sharded["caveat"]
    for mode, model_name in (("tensor", "vit"), ("expert", "moe_mlp")):
        block = sharded[mode]
        assert block["model"] == model_name
        assert block["requests_per_sec"] > 0
        assert block["vs_replicated"] > 0
        assert len(block["pairs"]) == 4
        curve = block["mesh_scaling"]
        assert [pt["mesh_devices"] for pt in curve] == [1, 2, 4]
        assert [pt["mesh_groups"] for pt in curve] == [4, 2, 1]
        assert all(pt["requests_per_sec"] > 0 for pt in curve)
        assert block["zero_steady_state_recompiles"] is True
    # Per bucket x mode compile rows landed under the @{mode} names.
    assert any("@tensor" in name for name in programs)
    assert any("@expert" in name for name in programs)

    # The MPMD pipeline block (ISSUE 12): one chain of per-chip stage
    # programs, the window>=stages vs window-1 stage-overlap speedup
    # (ABBA pairs), per-stage step walls + occupancy with the bottleneck
    # stage at 1.0, and the per bucket x stage zero-recompile verdict.
    # The engine-factory mode must NOT appear in the SPMD sharded block.
    assert "pipeline" not in sharded
    pp = report["pipeline_serving"]
    assert pp["model"] == "vit" and pp["stages"] == 2
    assert pp["window"] == 3 and pp["chains"] == 1
    assert isinstance(pp["stage_overlap_speedup"], (int, float))
    assert pp["stage_overlap_speedup"] > 0
    assert len(pp["pairs"]) == 5
    assert pp["requests_per_sec"] > 0
    assert sorted(pp["stage_step_ms"]) == ["s0", "s1"]
    occ = pp["stage_occupancy"]
    assert sorted(occ) == ["s0", "s1"] and max(occ.values()) == 1.0
    assert pp["zero_steady_state_recompiles"] is True
    # This CPU run must carry the BENCH_r05-style fallback caveat:
    # host-thread transfers say nothing about ICI.
    assert "CPU fallback" in pp["caveat"]
    assert "nothing about ICI" in pp["caveat"]
    # Per bucket x stage compile rows landed under the @pipeline names.
    assert any("@pipeline.s0" in name for name in programs)
    assert any("@pipeline.s1" in name for name in programs)

    # The precision sweep (ISSUE 14): one entry per registered
    # quantized precision with the ABBA-paired vs-f32 ratio, the
    # eval-batch agreement/accuracy deltas, and the per bucket x mode x
    # precision zero-recompile verdicts; CPU runs carry the BENCH_r05-
    # style caveat (host int8 says little about the TPU MXU/ICI).
    sweep = report["precision_sweep"]
    assert "CPU fallback" in sweep["caveat"]
    assert "MXU" in sweep["caveat"]
    assert isinstance(sweep["f32_accuracy"], float)
    for prec in ("bf16", "int8w", "int8"):
        block = sweep[prec]
        assert block["vs_f32"] > 0 and len(block["pairs"]) == 4
        assert block["requests_per_sec"] > 0
        assert 0.9 <= block["argmax_agreement_vs_f32"] <= 1.0
        assert isinstance(block["accuracy_delta_vs_f32"], float)
        assert block["max_logit_delta_vs_f32"] >= 0
        assert block["zero_steady_state_recompiles"] is True
    # Every registered mode x quantized precision got a verdict (the
    # LIVE registry, engine-factory modes included).
    modes = sweep["modes"]
    for mode in ("tensor", "expert", "pipeline"):
        for prec in ("bf16", "int8w", "int8"):
            assert modes[f"{mode}.{prec}"][
                "zero_steady_state_recompiles"] is True
    # Per bucket x precision compile rows landed under the .{prec} names.
    assert any(name.endswith("@bf16") for name in programs)
    assert any("@tensor.int8w" in name for name in programs)
    assert any("@pipeline.int8.s0" in name for name in programs)

    # The whole-program block (ISSUE 16): one fused ViT engine serving
    # both routes — the ABBA-paired fused-over-split ratio, the
    # host-work collapse, the staged-bytes ratio (float32 vs raw uint8
    # = 4x), donated-staging retirement, and the zero-recompile verdict
    # across BOTH planes. The fused compile rows carry the .fused tag
    # inside the bucket segment.
    wp = report["whole_program"]
    assert wp["model"] == "vit" and wp["images_per_request"] == 8
    assert wp["fused_over_split_speedup"] > 0
    assert len(wp["pairs"]) == 4
    assert wp["requests_per_sec"] > 0
    host = wp["host_preprocess_ms_per_request"]
    # The collapse itself: raw passthrough beats host normalization.
    assert host["fused"] < host["split"]
    bytes_ = wp["h2d_bytes_per_request"]
    assert bytes_["split"] == 8 * 28 * 28 * 4
    assert bytes_["fused"] == 8 * 28 * 28
    assert bytes_["ratio"] == 4.0
    assert wp["model_flops_per_image"] > 0
    assert wp["mfu"] is None  # no honest peak to divide by on CPU
    assert wp["donated_staging_retired"]["8"] > 0  # JSON keys: strings
    assert wp["zero_steady_state_recompiles"] is True
    assert "CPU fallback" in wp["caveat"]
    assert any(".fused@wp" in name for name in programs)

    # The overload block (ISSUE 15): goodput-vs-offered-load curve
    # through the priority batcher, per-class completions + p99, the
    # 70%-of-peak and interactive-below-batch verdicts, and the
    # autoscaler-actuation recompile verdict — all of which FAIL the
    # bench (exit 1) when violated.
    over = report["overload"]
    assert over["capacity_rps"] > 0
    assert [pt["offered_x"] for pt in over["points"]] == [1, 2, 5, 10]
    for pt in over["points"]:
        assert pt["offered_rps"] > 0
        assert pt["goodput_rps"] > 0
        assert set(pt["classes"]) <= {"interactive", "batch",
                                      "best_effort"}
    assert over["peak_goodput_rps"] > 0
    assert over["goodput_holds_at_overload"] is True
    assert over["interactive_p99_below_batch_p99"] is True
    top = over["points"][-1]
    # 10x offered load really was overload: most of it was shed, and
    # best_effort shed proportionally hardest (the watermark order).
    assert top["shed"] > top["completed"]
    auto = over["autoscale"]
    assert auto["actuated"] is True
    assert auto["zero_steady_state_recompiles_across_resizes"] is True
    assert [d["action"] for d in auto["resizes"]] == [
        "scale_up", "scale_down"]
    assert "CPU fallback" in over["caveat"]

    # The fleet block (ISSUE 17): two real loopback backends behind a
    # real router — the ABBA-paired routed-vs-direct overhead, the
    # open-loop goodput curve THROUGH the router (same 70%-of-peak
    # shed-not-collapse rule as the single-process block), and the
    # per-backend zero-recompile verdict across every routed drive.
    fleet = report["fleet"]
    assert fleet["ok"] is True
    assert fleet["backends"] == 2
    over_f = fleet["router_overhead"]
    assert over_f["pairs"] == 2
    assert over_f["direct_p50_ms"] > 0
    assert over_f["routed_p50_ms"] > 0
    assert over_f["p50_overhead_ratio"] > 0
    assert over_f["p99_overhead_ratio"] > 0
    good = fleet["goodput"]
    assert good["capacity_rps"] > 0
    assert len(good["points"]) == 2
    assert good["points"][0]["offered_x"] == 1.0
    assert good["points"][-1]["offered_x"] > 1.0
    assert all(pt["goodput_rps"] > 0 for pt in good["points"])
    assert good["holds_at_overload"] is True
    # Side-by-side with the single-process overload verdict.
    assert good["single_process_fraction_of_peak"] == \
        over["goodput_at_top_fraction_of_peak"]
    assert fleet["zero_steady_state_recompiles_per_backend"] is True
    assert fleet["router_stats"]["routable"] == 2
    assert "CPU fallback" in fleet["caveat"]

    # The economics block (ISSUE 19): zipf-duplicate drive through the
    # response cache — measured hit/miss p99 split, the warm-cache
    # goodput curve holding the 96%-of-peak bar at ~10x, the collapse
    # ratio, the live server cache + measured cost table, and the
    # zero-recompile verdict on the cached path.
    econ = report["economics"]
    assert econ["ok"] is True
    zd = econ["zipf_drive"]
    assert zd["zipf_exponent"] == 1.1
    assert zd["hit_rate"] > 0
    assert zd["hit_p99_ms"] > 0 and zd["miss_p99_ms"] > 0
    assert zd["hit_is_cheap"] is True
    assert zd["enforced_bar"] == 1.0  # the CPU bar; 0.1 on TPU
    good_e = econ["goodput"]
    assert good_e["capacity_rps"] > 0
    # The top point targets 10x but the open-loop rate is clamped at
    # 1500 rps, so on a fast cached path offered_x lands lower.
    assert good_e["points"][0]["offered_x"] == 1.0
    assert good_e["points"][-1]["offered_x"] > 1.0
    assert good_e["holds_at_overload"] is True
    assert good_e["single_process_fraction_of_peak"] == \
        over["goodput_at_top_fraction_of_peak"]
    assert econ["zero_steady_state_recompiles"] is True
    assert econ["collapse_ratio"] >= 0
    assert econ["server_cache"]["hits"] > 0
    assert econ["cost_model"]["buckets"] == [1, 8]
    assert "CPU fallback" in econ["caveat"]


def test_bench_serve_overload_verdicts_fail_loudly():
    """The overload verdicts really carry teeth: the injected failure
    hook (mirroring BENCH_ZERO_INJECT_RECOMPILE) must turn the line
    into exit 1 with the overload error named."""
    env = os.environ.copy()
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "BENCH_FORCE_CPU": "1",
        "BENCH_SERVE_REQUESTS": "64",
        "BENCH_SERVE_POOL_REQUESTS": "64",
        "BENCH_SERVE_CONCURRENCY": "8",
        "BENCH_SERVE_PRECISION_REQUESTS": "32",
        "BENCH_OVERLOAD_SECONDS": "0.5",
        "BENCH_OVERLOAD_POINTS": "1,2",
        "BENCH_OVERLOAD_INJECT_FAIL": "1",
        "BENCH_FLEET_SECONDS": "0.5",
        "BENCH_FLEET_PAIRS": "2",
        "BENCH_FLEET_REQUESTS": "16",
        "BENCH_FLEET_INJECT_FAIL": "1",
        "BENCH_ECONOMICS_SECONDS": "0.5",
        "BENCH_ECONOMICS_REQUESTS": "32",
        "BENCH_ECONOMICS_INJECT_FAIL": "1",
        "JAX_COMPILATION_CACHE_DIR": "",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--mode",
         "serve"],
        capture_output=True, text=True, timeout=540, env=env, cwd=REPO,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "overload" in report["error"]
    assert report["overload"]["goodput_holds_at_overload"] is False
    # The fleet injection hook carries teeth too (the overload error
    # outranks it in the message, but the verdict and exit gate hold).
    assert report["fleet"]["ok"] is False
    assert report["economics"]["ok"] is False
