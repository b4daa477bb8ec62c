"""``chip_smoke.py`` refuses to run anywhere but on a TPU with native kernels.

Each case runs the script in a child process with the CPU backend forced, so
none of them can reach a chip: the script must exit non-zero, say why, and
print no result line.
"""

import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "chip_smoke.py"


@pytest.mark.parametrize(
    "case,reason",
    [
        ("cpu", "JAX found no TPU"),
        ("interpret", "REPRO_PALLAS_INTERPRET is set"),
        ("alone", "no solver package"),
    ],
)
def test_chip_smoke_refuses_without_a_chip(case, reason, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_PALLAS_INTERPRET", None)
    script = SCRIPT
    if case == "interpret":
        env["REPRO_PALLAS_INTERPRET"] = "1"
    elif case == "alone":  # the script without the rest of the repo
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
    proc = subprocess.run(
        [sys.executable, str(script)], cwd=script.parent, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert reason in proc.stderr
    assert '"ok"' not in proc.stdout
