"""The bench orchestrator: a JAX-free parent that runs every stage as a
killable child with a plain timeout, gates on a liveness probe that
wants a TPU, and never reports a device number for a device it did not
find."""

import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_stage_timeout_kills_the_whole_process_group(tmp_path,
                                                     monkeypatch):
    """A stage that hangs costs its own timeout and leaves nothing
    behind: the grandchild it forked (which on the chip would still hold
    the device) dies with it, and what the stage had printed is kept."""
    sys.path.insert(0, REPO)
    import bench
    pidfile = tmp_path / "grandchild.pid"
    # stands in for the interpreter the orchestrator starts stages with:
    # whatever the stage, it forks a sleeper, prints a line, and hangs
    hang = tmp_path / "hanging_stage"
    hang.write_text(
        "#!%s\n"
        "import subprocess, sys, time\n"
        "p = subprocess.Popen([sys.executable, '-c', "
        "'import time; time.sleep(600)'])\n"
        "open(%r, 'w').write(str(p.pid))\n"
        "print('{\"partial\": 1}', flush=True)\n"
        "time.sleep(600)\n" % (sys.executable, str(pidfile)))
    hang.chmod(0o755)
    monkeypatch.setattr(sys, "executable", str(hang))
    t0 = time.monotonic()
    line, err = bench._stage_subprocess("mnist", 3)
    assert time.monotonic() - t0 < 30
    assert line == {"partial": 1}
    assert err == "stage mnist timeout after 3s"
    pid = int(pidfile.read_text())
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            break
        time.sleep(0.1)
    else:
        os.kill(pid, 9)
        pytest.fail("grandchild %d outlived its stage's timeout" % pid)


def test_peak_table_rejects_an_unknown_device_kind():
    """MFU is only reported against a published peak of the device the
    run found: an unknown kind is an error, never a default."""
    sys.path.insert(0, REPO)
    import bench
    assert bench.peak_bf16_flops("TPU v5 lite") == 197e12
    with pytest.raises(SystemExit, match="no published peak"):
        bench.peak_bf16_flops("cpu")


def test_orchestrator_reports_no_tpu_fast():
    """Liveness finds no TPU -> ONE schema-whole JSON line, a non-zero
    exit, and no stage burned: a run on the CPU must never print a
    number under a device's name."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["VELES_BENCH_BUDGET"] = "600"
    proc = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300, cwd=REPO)
    assert proc.returncode == 2, (proc.returncode, proc.stderr[-800:])
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, lines
    line = json.loads(lines[0])
    assert line["metric"] == "alexnet_train_images_per_sec_per_chip"
    assert line["value"] is None and line["vs_baseline"] is None
    assert "no TPU" in line["error"] and "'cpu'" in line["error"]
    assert "stage alexnet_f32" not in proc.stderr


def test_stage_plan_is_headline_first():
    """A budget that runs out must cost the optional tail, not the
    headline: the plan keeps the liveness gate then the headline scans
    ahead of the optional hand-kernel stages."""
    sys.path.insert(0, REPO)
    import bench
    order = [s for s, _ in bench.STAGE_PLAN]
    assert order[0] == "liveness"
    assert order[1] == "alexnet_f32"
    assert order.index("alexnet_bf16") < order.index("pallas_lrn")
    assert order.index("alexnet_f32") < order.index("precise_gemm")
    # the cold-start stage (ISSUE 5) rides in the optional tail with
    # its own timeout budget, behind every headline training stage
    assert "cold_start" in order
    assert order.index("cold_start") > order.index("mnist")


def test_last_json_line_recovers_partial_output():
    sys.path.insert(0, REPO)
    import bench
    text = 'noise\n{"a": 1}\nmore noise\n{"b": 2, "spread": {}}\ntrailing'
    assert bench._last_json_line(text) == {"b": 2, "spread": {}}
    assert bench._last_json_line("no json here") is None
