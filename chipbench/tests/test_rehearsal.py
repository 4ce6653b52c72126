"""Each cell's whole path, end to end on the CPU at a small size: the
program's Engine and Scheduler, the wall-clock serving loop, the metric
readers and the reference comparison. And no result off a TPU."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

HERE = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402
from small import CELLS, PEAKS, small_cell  # noqa: E402


@pytest.mark.parametrize("name", CELLS)
def test_cell_serves_and_checks(name):
    cell = small_cell(name)
    res, code = run.run_cell(cell, seed=2**31 + 17, seconds=1.0, trace=False,
                             devices=jax.devices()[:1], peaks=PEAKS)
    assert code == 0 and res["correct"], res
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert res["attempted"] > 0 and res["failed"] == 0
    chk = res["checks"]["served_logit_gap"]
    assert 0 <= chk["value"] <= chk["limit"]
    assert list(res)[-1] == "checks"
    assert set(res["memory"]) == {"process_peak_bytes",
                                  "window_in_use_max_bytes"}
    json.dumps(res)


def test_rate_turns_a_backlog_into_steady_arrivals():
    cell = run.with_rate(small_cell("qwen3-1.7b.offline"), 6.0)
    assert cell.mix["arrival"] == {"process": "poisson", "rate_rps": 6.0}
    res, code = run.run_cell(cell, seed=7, seconds=1.0, trace=False,
                             devices=jax.devices()[:1], peaks=PEAKS)
    assert code == 0 and res["correct"], res
    # open loop: the requests due in the window, about rate x seconds
    assert 3 <= res["attempted"] <= 9


def test_traced_run_reads_host_metrics(tmp_path):
    cell = small_cell("qwen3-1.7b.chat")
    res, code = run.run_cell(cell, seed=5, seconds=1.0, trace=True,
                             devices=jax.devices()[:1], peaks=PEAKS,
                             trace_dir=tmp_path / "trace")
    assert code == 0 and res["correct"]
    # the CPU has no device plane: only host-side metrics are read
    assert {"tick_ms.online", "queue_wait_p95_ms"} <= set(res["metrics"])
    assert res["device"]["window_s"] > 0
    assert {"device_ops", "idle_gaps"} <= set(res["breakdown"])


def test_no_result_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(HERE / "run.py"), "--workload",
                        "qwen3-1.7b.chat", "--seed", str(2**31 + 3),
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr
