"""chip_smoke.py: the parent stays off jax, a rehearsal can never be
read as a chip pass, and a failing phase fails the script."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402  (stdlib-only by contract: see below)


def test_parent_never_imports_jax():
    """The chip belongs to one process at a time: a parent that touched
    jax would hold it and starve every child. Subprocess-pinned, since
    this pytest process imported jax long ago."""
    code = (
        "import sys; import chip_smoke; "
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith(('jax.', 'dstack_tpu'))]; "
        "assert not bad, bad"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True, text=True,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stderr[-800:]


def test_rehearsal_completes_serve_and_is_no_chip_pass():
    """llama-tiny on the CPU through the real server child: warmup,
    the five request shapes, SIGTERM. Every line names the platform it
    really ran on; none says ok:true at top level without it."""
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearse", "--only", "serve"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-2000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    phases = {l["phase"]: l for l in lines[:-1]}
    assert set(phases) == {"device", "serve"}
    for line in phases.values():
        assert line["ok"] and line["rehearse"] and line["platform"] == "cpu"
    serve = phases["serve"]
    assert serve["prefix_hits"] >= 1
    assert serve["throughput_info"]["measured_on"]["platform"] == "cpu"
    assert len(serve["cache_entries"]) == 2
    last = lines[-1]
    assert "ok" not in last and last["device"]["platform"] == "cpu"


@pytest.mark.parametrize("rehearse,only,says_ok", [
    (False, None, True),
    (True, None, False),
    (False, "serve", False),
    (True, "serve", False),
])
def test_only_a_whole_chip_run_says_ok(rehearse, only, says_ok):
    device = {"platform": "cpu" if rehearse else "tpu", "kind": "k", "count": 1}
    line = chip_smoke.result_line(rehearse, only, device)
    assert ("ok" in line) == says_ok
    assert line["device"] == device
    if says_ok:
        # exactly the contract line, nothing else
        assert line == {"ok": True, "device": device}


def test_failing_phase_fails_the_script(monkeypatch, capsys):
    """A child that exits non-zero (finetune on a model that does not
    exist) ends the run non-zero, with its stderr tail in the phase
    line and no result line after it."""
    monkeypatch.setattr(chip_smoke, "REHEARSE_MODEL", "no-such-model")
    monkeypatch.setitem(chip_smoke.VOCAB, "no-such-model", 512)
    with pytest.raises(SystemExit) as exit_info:
        chip_smoke.main(["--rehearse", "--only", "train"])
    assert exit_info.value.code == 1
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    assert [l["phase"] for l in lines] == ["device", "train"]
    assert lines[-1]["ok"] is False
    assert "no-such-model" in lines[-1]["error"]


def test_without_the_repository_it_fails(tmp_path):
    """Alone in a directory the script is nothing: non-zero, no result."""
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text()
    )
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearse"], cwd=tmp_path,
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
