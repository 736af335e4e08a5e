"""The benchmark refuses any platform but a TPU: a non-zero exit and no
result line."""
import os
import subprocess
import sys

from chipbench import registry, run


def test_main_refuses_the_cpu(capsys):
    rc = run.main(["--workload", "revgeo-10m.bulk", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "runs on the chip only" in out.err


def test_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "revgeo-10m.bulk", "--seed", str(2 ** 31 + 1),
         "--seconds", "10", "--trace", "1"],
        cwd=registry.REPO, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
