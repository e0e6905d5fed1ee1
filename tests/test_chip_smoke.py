"""``chip_smoke.py`` rehearsed on the CPU.

The script's session phase runs here at ``reduce_config`` width with the
Pallas kernels in interpret mode, and all five of its checks must pass;
its ``--chips 4`` phase runs on four forced host devices and must meet
the parity bar. Its ``main`` must refuse the CPU: exit nonzero and print
no result line.
"""

import importlib.util
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

SMALL_ARGV = [
    "--tenants", "4", "--rounds", "1", "--samples-per-round", "8",
    "--seq", "16", "--prompt-len", "16", "--gen", "8", "--adapt-epochs", "2",
    "--batch-per-tenant", "4", "--rank", "8", "--use-kernel", "--scheduler",
]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve their module here
    spec.loader.exec_module(mod)
    return mod


def test_session_checks_pass_at_reduced_width(chip_smoke):
    out = chip_smoke.session_checks(SMALL_ARGV)
    names = [c.name for c in out["checks"]]
    assert names == [
        "a_requests", "b_adapt_losses", "c_kernels_vs_oracles",
        "d_logits_vs_f32", "e_adapted_differs",
    ]
    failed = [(c.name, c.detail) for c in out["checks"] if not c.ok]
    assert not failed, failed
    assert out["replay_equal"]
    labels = [p[0] for p in out["phases"]]
    assert {"serve/base", "adapt/r0", "serve/mixed/r0", "check/kernels",
            "check/reference"} <= set(labels)
    assert all(run_s >= 0 for _, _, run_s in out["phases"])


def test_mesh_parity_at_reduced_width():
    """``--chips 4``'s phase on four forced host devices (a subprocess: the
    device count must be set before JAX starts): the float32 2x2 mesh
    session meets ``repro.launch.run``'s parity bar against its twin, and
    every device holds a share of the backbone."""
    code = (
        "import importlib.util, sys\n"
        "spec = importlib.util.spec_from_file_location('chip_smoke', 'chip_smoke.py')\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "sys.modules['chip_smoke'] = mod\n"
        "spec.loader.exec_module(mod)\n"
        "argv = [a for a in mod.MESH_ARGV if a != '--full']\n"
        "argv[argv.index('--seq') + 1] = '16'\n"
        "argv[argv.index('--prompt-len') + 1] = '16'\n"
        "argv[argv.index('--gen') + 1] = '8'\n"
        "out = mod.mesh_parity(argv)\n"
        "print('DIFFS', out['diffs'])\n"
        "print('DEVICES', sorted(d for d, b in out['backbone'].items() if b > 0))\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=4 " + env.get("XLA_FLAGS", "")
    )
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, env=env, cwd=ROOT,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert "DIFFS []" in res.stdout, res.stdout[-2000:]
    devices = res.stdout.split("DEVICES ")[1].splitlines()[0]
    assert devices.count("CPU_") == 4, devices


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_the_cpu(chip_smoke, capsys, argv):
    assert chip_smoke.main(argv) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "no TPU" in captured.err
