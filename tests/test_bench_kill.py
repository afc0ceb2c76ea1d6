"""bench.py kill-path hardening (ISSUE 9 satellite): a SIGTERM delivered
mid-extra (what ``timeout -k`` sends before SIGKILL) must still leave a
parseable final JSON line on stdout AND a parseable atomic partial file —
a pre-PR-1 failure mode was rc=124 with parsed=null."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


@pytest.mark.parametrize("sig", [signal.SIGTERM])
def test_sigterm_mid_extra_yields_parseable_output(tmp_path, sig):
    partial = tmp_path / "bench_partial.json"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SLATE_TPU_BENCH_PARTIAL"] = str(partial)
    env.pop("SLATE_TPU_OBS_MEM", None)
    proc = subprocess.Popen(
        [sys.executable, BENCH, "--selftest-kill"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=str(tmp_path),
    )
    try:
        # wait for the harness to reach the blocked mid-extra state
        deadline = time.time() + 120
        ready = False
        lines = []
        while time.time() < deadline:
            line = proc.stderr.readline()
            if not line:
                time.sleep(0.05)
                continue
            lines.append(line)
            if "SELFTEST_READY" in line:
                ready = True
                break
        assert ready, f"selftest never armed: {''.join(lines)[-2000:]}"
        proc.send_signal(sig)
        out, err = proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 124, (proc.returncode, out[-500:])
    # the driver's tail parser: the LAST parsable JSON line wins
    parsed = None
    for line in reversed(out.strip().splitlines()):
        if line.startswith("{"):
            try:
                parsed = json.loads(line)
                break
            except json.JSONDecodeError:
                continue
    assert parsed is not None, f"no parsable line in tail: {out[-500:]}"
    assert "metric" in parsed and isinstance(parsed.get("value"), (int, float))
    # the SIGKILL-proof twin: the atomically-rewritten partial file
    assert partial.exists()
    twin = json.loads(partial.read_text())
    assert twin["metric"] == parsed["metric"]
