"""chip_smoke.py off the chip: every phase runs, and the answer is no.

The script is the proof that the main path runs on a TPU, so what matters
here is that nothing else can pass for one: the CPU rehearsal (`--tiny`)
goes through the elastic CLI, the agent, both incarnations, the
checkpoint and the compile cache, and still exits non-zero with
`"ok": false` and the platform it found. The parent stays off jax the
whole way (it asserts so itself before every child it starts).
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cwd, script, *flags, timeout=420):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # one CPU device: the tiny batch of 2 is not for the harness's 8
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    proc = subprocess.run(
        [sys.executable, "-X", "importtime", script, *flags],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)
    lines = [line for line in proc.stdout.splitlines() if line.strip()]
    return proc, [json.loads(line) for line in lines]


def test_tiny_rehearsal_runs_every_phase_and_says_no():
    proc, records = _run(REPO, os.path.join(REPO, "chip_smoke.py"), "--tiny")
    assert proc.returncode != 0
    assert records[-1] == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    # the parent never imported jax (-X importtime lists every import of
    # the PARENT process on its stderr; children log to a file)
    imported = {line.split("|")[-1].strip()
                for line in proc.stderr.splitlines()
                if line.startswith("import time:")}
    assert "argparse" in imported and "jax" not in imported
    checks = {}
    for record in records:
        if "check" in record:
            checks.setdefault(record["check"], []).append(record["ok"])
    # what only a chip can pass, failed — in both incarnations
    for name in ("backend_is_tpu", "device_kind_in_peak_table",
                 "step_program_kernels_in_program"):
        assert checks[name] == [False, False], name
    # everything else is the product's own behaviour, and it held
    for name in ("fresh_start", "restored_from_checkpoint",
                 "sampler_position_restored", "second_compile_is_cache_load",
                 "resumed_run_reproduces_uninterrupted_loss"):
        assert checks[name] == [True], name
    for name in ("step_program_aot_compiled", "first_step_used_aot",
                 "steps_taken", "losses_finite", "checkpoint_committed"):
        assert checks[name] == [True, True], name
    restores = [r for r in records if r.get("phase") == "restore_or_init"]
    assert [r["last_restore_source"] for r in restores][0] == "init"
    assert restores[1]["last_restore_source"] in ("orbax", "peer", "mixed")
    assert restores[1]["start_step"] == 4
    # through the elastic CLI, and the cache where the environment said
    plan = records[0]
    assert plan["compile_cache_dir"] == os.environ[
        "JAX_COMPILATION_CACHE_DIR"]
    exits = [r for r in records if r.get("phase") == "exit"]
    assert [r["incarnation"] for r in exits] == ["train1", "train2"]
    with open(os.path.join(REPO, "chiprun_out", "chip_smoke.log")) as f:
        assert f.read().count("-m dlrover_tpu.run --standalone") == 2


def test_full_width_without_a_chip_fails_at_once():
    """As the driver runs it, in a sandbox with no accelerator: through
    the CLI to the worker, which finds no TPU and stops there — it does
    not start a 1.47B model under the Pallas interpreter."""
    proc, records = _run(REPO, os.path.join(REPO, "chip_smoke.py"),
                         timeout=180)
    assert proc.returncode != 0
    assert records[-1] == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert [r["incarnation"] for r in records
            if r.get("phase") == "exit"] == ["train1"]
    assert not any(r.get("phase") == "restore_or_init" for r in records)


def test_alone_in_a_directory_it_fails(tmp_path):
    """With nothing else of the repo beside it there is no program to
    run: non-zero, `"ok": false`, no device."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc, records = _run(str(tmp_path), "chip_smoke.py", timeout=60)
    assert proc.returncode != 0
    assert records[-1]["ok"] is False and records[-1]["device"] == {}


@pytest.mark.parametrize("platforms,probe_rc,expected", [
    ("cpu", 1, 1),          # CPU harness: a failed probe reads as 1
    ("", 1, RuntimeError),  # accelerator expected: a failed probe raises
    ("", 0, 4),             # the probe's answer, when it has one
])
def test_device_probe_failure_is_an_error_off_cpu(monkeypatch, platforms,
                                                  probe_rc, expected):
    from dlrover_tpu import run as run_cli

    monkeypatch.delenv("DLROVER_TPU_DEVICES_PER_NODE", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.setattr(
        subprocess, "run",
        lambda *a, **k: subprocess.CompletedProcess(
            a, probe_rc, stdout="4\n", stderr="no TPU found"))
    if expected is RuntimeError:
        with pytest.raises(RuntimeError, match="no TPU found"):
            run_cli._detect_devices()
    else:
        assert run_cli._detect_devices() == expected
