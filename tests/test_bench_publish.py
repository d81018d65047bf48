"""End-to-end smoke of ``bench.py --mode publish`` on the CPU backend:
the acceptance line for delta distribution. The report must carry the
``publish`` block (whole-file baseline vs chunked publish costs) and
the ``fleet`` block (3-fetcher convergence with loopback gossip), with
the headline ratio asserted under the ISSUE's 30% bar — so the delta
BENCH schema can't silently rot while CI exercises only the in-process
pieces. The inject-fail twin pins that a broken assertion exits 1 with
the failure named in the JSON line, never a silent green."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = [pytest.mark.slow, pytest.mark.distrib]


def _run(extra_env):
    env = os.environ.copy()
    env.update({
        "JAX_PLATFORMS": "cpu",
        # Small chunk budget: the linear model must span several chunks
        # or the adjacency measurement degenerates to one-chunk leaves.
        "BENCH_PUBLISH_CHUNK_MB": "0.25",
        "BENCH_PUBLISH_BACKENDS": "3",
        "JAX_COMPILATION_CACHE_DIR": "",
    })
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--mode", "publish"],
        capture_output=True, text=True, timeout=540, env=env, cwd=REPO,
    )


def test_bench_publish_reports_delta_and_fleet_blocks():
    proc = _run({})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])

    assert report["metric"] == \
        "mnist_delta_publish_adjacent_fleet_bytes_fraction"
    assert report.get("error") is None
    # The headline: adjacent-epoch fleet bytes as a fraction of shipping
    # the whole file to every backend — the ISSUE's <30% acceptance bar.
    assert 0 < report["value"] < 0.30
    assert report["vs_baseline"] > 1

    pub = report["publish"]
    assert pub["chunk_mb"] == 0.25
    assert pub["whole_file_bytes"] > 0
    assert 0 < pub["cold_chunk_bytes"]
    # An adjacent epoch re-publishes only the dirtied leaf's chunks.
    assert 0 < pub["adjacent_new_chunk_bytes"] < pub["cold_chunk_bytes"]
    assert pub["adjacent_publish_bytes_fraction"] < 0.30
    for key in ("whole_file_publish_s", "cold_publish_s",
                "adjacent_publish_s"):
        assert pub[key] >= 0

    fleet = report["fleet"]
    assert fleet["backends"] == 3
    assert fleet["cold_fetch_bytes"] > 0
    assert 0 < fleet["adjacent_fetch_bytes"] < fleet["cold_fetch_bytes"]
    assert fleet["adjacent_fleet_bytes_fraction"] == report["value"]
    assert fleet["delta_under_30pct_of_whole_file"] is True
    # The gossip ordering proof: non-seed fetchers pulled every missing
    # chunk from the peer endpoint, and the source dir saw ZERO reads
    # from them — peers-before-source, measured not asserted-by-code.
    assert fleet["gossip_peer_bytes"] > 0
    assert fleet["non_seed_source_bytes"] == 0
    assert fleet["dirty_leaves"] > 0 and fleet["clean_leaves"] > 0

    # BENCH_r05 CPU labeling: the caveat says what this line measured.
    assert "caveat" in report and report["measured_at"]


def test_bench_publish_inject_fail_exits_loudly():
    proc = _run({"BENCH_PUBLISH_INJECT_FAIL": "1"})
    assert proc.returncode == 1, proc.stdout + proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["error"] and "BENCH_PUBLISH_INJECT_FAIL" in report["error"]
