"""The device is never hidden: no-chip failures, and the smoke's rehearsal.

``chip_smoke.py`` proves the main path on the TPU; these tests prove on
the CPU (tier-1, seconds each) that (a) every entry point that means
"the TPU" FAILS without one instead of quietly running on the CPU, and
(b) the smoke's own phase code — spawn the real CLIs, parse their logs,
drive the HTTP traffic, drain — works end to end at toy size with
``--device=cpu`` children, so a chip run is never spent debugging it.
"""

import json
import os
import re
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from tensorflow_examples_tpu.core import device  # noqa: E402

# One CPU device per child, whatever the harness set for this process.
_ENV = {
    **{k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
    "JAX_PLATFORMS": "cpu",
}


def _run(argv, timeout=120, **env):
    return subprocess.run(
        [sys.executable, *argv], cwd=REPO, env={**_ENV, **env},
        capture_output=True, text=True, timeout=timeout,
    )


# --------------------------------------------------------------- no chip


def test_chip_smoke_fails_without_a_chip():
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0
    assert "'cpu'" in r.stdout  # names the platform it found
    assert '"ok"' not in r.stdout and "PASS {" not in r.stdout.replace(
        "[device] PASS", ""
    )


def test_chip_smoke_fails_outside_the_checkout(tmp_path):
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        (tmp_path / "chip_smoke.py").write_text(f.read())
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=_ENV,
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_device_tpu_refuses_the_cpu():
    r = _run(
        [os.path.join("examples", "mnist", "train.py"), "--device=tpu",
         "--train_steps=1"]
    )
    assert r.returncode != 0
    assert "(--device=tpu) but JAX's default backend is 'cpu'" in r.stderr
    assert "step 1" not in r.stderr  # stopped at start-up, trained nothing


def test_bench_refuses_the_cpu():
    r = _run(["bench.py", "--bench=mnist"])
    assert r.returncode != 0
    assert "JAX's default backend is 'cpu'" in r.stderr
    assert r.stdout.strip() == ""  # no record from a CPU


def test_pallas_interpret_rule(monkeypatch):
    device.pallas_interpret.cache_clear()
    try:
        assert device.pallas_interpret("k") is True  # cpu: the tests
        device.pallas_interpret.cache_clear()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert device.pallas_interpret("k") is False
        device.pallas_interpret.cache_clear()
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="no rule for platform 'gpu'"):
            device.pallas_interpret("k")
    finally:
        device.pallas_interpret.cache_clear()


# --------------------------------------------------------- compile cache

# Spelled in two halves so this file is not itself a hit for the
# one-assignment scan below.
_KEY = "jax_compilation_" + "cache_dir"


def test_compile_cache_has_one_fixed_home(monkeypatch):
    before = getattr(jax.config, _KEY)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert device.enable_compile_cache() == os.path.join(
            REPO, ".jax_cache"
        )
        assert getattr(jax.config, _KEY) == os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update(_KEY, before)


def test_compile_cache_env_var_wins(tmp_path):
    r = _run(
        ["-c",
         "import jax\n"
         "from tensorflow_examples_tpu.core.device import "
         "enable_compile_cache\n"
         "print(enable_compile_cache())\n"
         "print(jax.config.jax_compilation_cache_dir)"],
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
    )
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(tmp_path)] * 2


def test_one_assignment_of_the_cache_dir_in_the_repo():
    """``git grep jax_compilation_cache_dir`` finds one assignment —
    checked by walking the tree, so it also holds in a plain checkout."""
    pat = re.compile(r"""update\(\s*["']""" + _KEY)
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".") and d not in (
            "chiprun_out", "chiprun_tree", "__pycache__", "build",
        )]
        for name in files:
            if name.endswith((".py", ".sh")):
                path = os.path.join(root, name)
                with open(path, errors="replace") as f:
                    if pat.search(f.read()):
                        hits.append(os.path.relpath(path, REPO))
    assert hits == [
        os.path.join("tensorflow_examples_tpu", "core", "device.py")
    ]


# ------------------------------------------------------------- rehearsal

TOY = (
    "--vocab_size=256", "--seq_len=128", "--num_layers=2", "--num_heads=2",
    "--d_model=64", "--global_batch_size=8",
)


@pytest.mark.timeout(300)
def test_rehearsal_train_then_serve_on_cpu(tmp_path, monkeypatch):
    """train.py -> checkpoint -> serve.py (paged pool, prefix cache,
    the Pallas paged-decode kernel in interpret mode) -> SIGTERM, through
    the smoke's own phase functions."""
    monkeypatch.delenv("XLA_FLAGS")  # children: one CPU device each
    work, out = str(tmp_path / "work"), str(tmp_path / "out")
    os.makedirs(out)
    train = chip_smoke.train_phase(
        work, out, device="cpu", flags=TOY, steps=6, timeout=240
    )
    assert train["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert train["loss_last"] < train["loss_first"]
    serve = chip_smoke.serve_phase(
        work, out, device="cpu", attention="paged_flash", vocab_size=256,
        flags=TOY, start_timeout=240,
    )
    assert serve["restored_step"] == 6
    assert serve["requests_ok"] == serve["requests_sent"] == 10
    assert serve["tokens"] == 158  # every request's full max_new_tokens
    assert serve["prefix_hit_rate"] > 0
    assert serve["post_warmup_recompiles"] == 0
    assert "<unknown>" not in serve["late_host_compiles"]
    assert serve["placement"].startswith("params on 1 ")
    with open(os.path.join(out, "serve_paged_flash_greedy.json")) as f:
        assert len(json.load(f)) == 9  # the sampled request is not in it
