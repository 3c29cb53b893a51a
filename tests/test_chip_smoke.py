"""chip_smoke.py refuses to run anywhere but on a TPU, and says why in
one line, before it prints any result."""
import os
import shutil
import subprocess
import sys

from conftest import REPO


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_with_one_line():
    out = _run(REPO, "chip_smoke.py")
    assert out.returncode != 0
    assert out.stdout == ""
    assert out.stderr.strip().splitlines() == [
        "chip_smoke: no TPU (JAX found cpu)"]


def test_script_alone_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run(tmp_path, "chip_smoke.py")
    assert out.returncode != 0
    assert out.stdout == ""


def test_phases_run_at_tiny_size(monkeypatch):
    """Every chip phase, at the reduced width on CPU: the fused kernels
    in interpret mode, HLO and memory probes stubbed (no TPU here)."""
    import jax

    if REPO not in sys.path:
        monkeypatch.syspath_prepend(REPO)
    import chip_smoke as cs
    from repro.api import Supernode
    from repro.configs.base import get_config
    from repro.core.compat import host_memory_kind
    from repro.kernels import ops

    real_cfg, real_hlo = cs.serve_config, cs.step_hlo
    monkeypatch.setattr(cs, "serve_config",
                        lambda **o: real_cfg(**{"kernels": "fused", **o}))
    monkeypatch.setattr(cs, "step_hlo", lambda srv, p: tuple(
        t + "tpu_custom_call" for t in real_hlo(srv, p)))
    monkeypatch.setattr(cs, "peak_bytes", lambda dev: -1)
    monkeypatch.setattr(cs, "TRAIN_SEQ", 128)
    cfg = get_config(cs.ARCH).reduced()
    params = cs.init_params(cfg, 0)
    prompts = cs.make_prompts(cfg, cs.N_REQ, cs.PROMPT_LO, cs.PROMPT_HI, 0)
    session = Supernode(None, devices=jax.devices()[:1])
    dev = jax.devices()[0]
    toks = cs.phase_serve(session, cfg, params, prompts, cs.compile_clock(),
                          dev)
    cs.phase_host_tier(session, cfg, params,
                       cs.make_prompts(cfg, cs.SEATS, cs.PROMPT_LO, 256, 1),
                       host_memory_kind())
    cs.phase_parity(session, cfg, params, prompts[:cs.PARITY_REQ])
    # the mismatch report: first differing token and its top-2 gap
    other = [list(t) for t in toks[:2]]
    other[1][3] = (other[1][3] + 1) % cfg.vocab_size
    req, step, gap = cs.first_divergence(cfg, params, prompts[:2], toks[:2],
                                         other)
    assert (req, step) == (1, 3) and gap >= 0.0
    ops.set_mode("pallas_interpret")
    try:
        cs.phase_kernels(get_config(cs.ARCH), 0)
    finally:
        ops.set_mode("auto")
    cs.phase_train(session, cfg, dev)
