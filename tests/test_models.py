"""Per-architecture smoke + cache-consistency tests.

For every assigned architecture (reduced config): one train step on CPU
asserting finite loss and gradient flow, and prefill+decode logits
matching the teacher-forced forward exactly (f32).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import build_model
from repro.optim.adamw import OptConfig
from repro.training import step as training_step

KEY = jax.random.PRNGKey(0)
B, S = 2, 16


def _inputs(cfg, key, batch=B, seq=S):
    toks = jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)
    kw = {}
    if cfg.frontend == "vision_patches":
        kw["frontend_embeds"] = jax.random.normal(
            key, (batch, cfg.frontend_tokens, cfg.d_model), jnp.float32
        )
    if cfg.is_encoder_decoder:
        kw["enc_embeds"] = jax.random.normal(
            key, (batch, seq, cfg.d_model), jnp.float32
        )
    return toks, kw


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_loss_and_cache_consistency(arch):
    cfg = get_config(arch, reduced=True)
    model = build_model(cfg)
    params = model.init(KEY)

    # --- forward & loss: shapes + finiteness ---
    toks, kw = _inputs(cfg, KEY)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}
    if "frontend_embeds" in kw:
        batch["patch_embeds"] = kw["frontend_embeds"]
    if "enc_embeds" in kw:
        batch["enc_embeds"] = kw["enc_embeds"]
    loss, metrics = jax.jit(lambda p, b: model.loss(p, b))(params, batch)
    assert np.isfinite(float(loss)), (arch, loss)
    assert float(loss) > 0

    logits, _ = model.forward(params, toks, frontend_embeds=kw.get("frontend_embeds"),
                              enc_embeds=kw.get("enc_embeds"), dtype=jnp.float32)
    F = cfg.frontend_tokens if cfg.frontend == "vision_patches" else 0
    assert logits.shape == (B, S + F, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))

    # --- prefill + decode == teacher-forced forward (f32 exact) ---
    pre = S - 2
    last, cache = model.prefill(
        params, toks[:, :pre], kv_len=S + 4, dtype=jnp.float32,
        frontend_embeds=kw.get("frontend_embeds"), enc_embeds=kw.get("enc_embeds"),
    )
    np.testing.assert_allclose(
        np.asarray(last), np.asarray(logits[:, F + pre - 1]), atol=2e-3, rtol=1e-3
    )
    for i in range(2):
        step_logits, cache = model.decode_step(
            params, cache, toks[:, pre + i : pre + i + 1], dtype=jnp.float32
        )
        np.testing.assert_allclose(
            np.asarray(step_logits),
            np.asarray(logits[:, F + pre + i]),
            atol=2e-3,
            rtol=1e-3,
        )


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mixtral-8x7b", "mamba2-2.7b"])
def test_train_step_decreases_loss(arch):
    cfg = get_config(arch, reduced=True)
    model = build_model(cfg)
    state = training_step.init_state(model, KEY)
    step = jax.jit(
        training_step.make_train_step(model, OptConfig(lr=1e-2, warmup_steps=1),
                                      remat=None)
    )
    toks = jax.random.randint(KEY, (4, 32), 0, cfg.vocab_size)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, 1)}
    losses = []
    for _ in range(5):
        state, m = step(state, batch)  # same batch: loss must drop
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
    assert all(np.isfinite(l) for l in losses)


def test_param_counts_match_analytic():
    """Declared params match the analytic count used for MODEL_FLOPS."""
    from repro.models.params import count_params

    for arch in ARCHS:
        cfg = get_config(arch, reduced=True)
        model = build_model(cfg)
        params = model.init(KEY)
        n = count_params(params)
        a = cfg.num_params()
        assert abs(n - a) / max(a, 1) < 0.02, (arch, n, a)


def test_microbatching_equivalence():
    """Grad accumulation over microbatches == single big batch."""
    cfg = get_config("qwen2-0.5b", reduced=True)
    model = build_model(cfg)
    state1 = training_step.init_state(model, KEY)
    state2 = jax.tree.map(lambda x: x, state1)
    toks = jax.random.randint(KEY, (8, 16), 0, cfg.vocab_size)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, 1)}
    s1 = jax.jit(training_step.make_train_step(model, OptConfig(), microbatches=1, remat=None))
    s4 = jax.jit(training_step.make_train_step(model, OptConfig(), microbatches=4, remat=None))
    n1, m1 = s1(state1, batch)
    n4, m4 = s4(state2, batch)
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 5e-3
    for a, b in zip(jax.tree.leaves(n1["params"]), jax.tree.leaves(n4["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-3)


def test_kv_quant_decode_close_to_exact():
    """int8 KV cache: decode logits within 1% of the f32-cache path."""
    from repro.models.transformer import LM

    cfg = get_config("granite-8b", reduced=True)
    m0, mq = LM(cfg), LM(cfg, kv_quant=True)
    params = m0.init(KEY)
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    ref, _ = m0.forward(params, toks, dtype=jnp.float32)
    _, cache = mq.prefill(params, toks[:, :14], kv_len=24, dtype=jnp.float32)
    assert cache["blocks"]["sub0"]["attn"]["k_q"].dtype == jnp.int8
    scale = float(jnp.max(jnp.abs(ref)))
    for i in range(2):
        logits, cache = mq.decode_step(
            params, cache, toks[:, 14 + i : 15 + i], dtype=jnp.float32
        )
        err = float(jnp.max(jnp.abs(logits - ref[:, 14 + i])))
        assert err / scale < 0.02, (i, err, scale)


def test_causality_property():
    """Changing future tokens must not change past logits (all archs with
    attention; the cache-consistency test already covers SSM recurrence)."""
    for arch in ("granite-8b", "gemma2-2b", "jamba-v0.1-52b"):
        cfg = get_config(arch, reduced=True)
        model = build_model(cfg)
        params = model.init(KEY)
        t1 = jax.random.randint(KEY, (1, 12), 0, cfg.vocab_size)
        t2 = t1.at[:, 8:].set((t1[:, 8:] + 7) % cfg.vocab_size)
        l1, _ = model.forward(params, t1, dtype=jnp.float32)
        l2, _ = model.forward(params, t2, dtype=jnp.float32)
        np.testing.assert_allclose(
            np.asarray(l1[:, :8]), np.asarray(l2[:, :8]), atol=1e-5,
            err_msg=arch,
        )


def test_sliding_window_property():
    """Tokens outside the L-layer receptive field (L x window) must not
    affect the last logit; tokens just inside it must."""
    cfg = get_config("mixtral-8x7b", reduced=True)  # 2 layers, window=8
    model = build_model(cfg)
    params = model.init(KEY)
    w, L = cfg.sliding_window, cfg.num_layers
    S = L * w + 12
    t1 = jax.random.randint(KEY, (1, S), 0, cfg.vocab_size)
    # outside the receptive field of the last position: < S-1 - L*w
    cut = S - 1 - L * w
    t2 = t1.at[:, :cut].set((t1[:, :cut] + 3) % cfg.vocab_size)
    l1, _ = model.forward(params, t1, dtype=jnp.float32)
    l2, _ = model.forward(params, t2, dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(l1[:, -1]), np.asarray(l2[:, -1]), atol=1e-5
    )
    # sanity: a change INSIDE the window does propagate
    t3 = t1.at[:, S - 2].set((t1[:, S - 2] + 3) % cfg.vocab_size)
    l3, _ = model.forward(params, t3, dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(l3[:, -1] - l1[:, -1]))) > 1e-4


def test_unknown_attention_impl_raises():
    cfg = get_config("qwen2-0.5b", reduced=True)
    with pytest.raises(ValueError, match="unknown attention impl"):
        build_model(cfg, impl="flash")


def test_pallas_impl_registers_itself_and_runs_the_kernel(monkeypatch):
    """Building a model with impl="pallas" imports kernels/ops.py on its
    own, and the model's attention runs the kernels (interpret mode here)
    with no fallback to the jnp path."""
    import sys

    import repro.kernels
    from repro.models import layers

    # a process in which nobody has imported the kernels yet
    monkeypatch.delitem(sys.modules, "repro.kernels.ops", raising=False)
    monkeypatch.delattr(repro.kernels, "ops", raising=False)
    monkeypatch.delitem(layers.SDPA_IMPL, "pallas", raising=False)

    cfg = get_config("qwen2-0.5b", reduced=True)
    ref = build_model(cfg, impl="jnp")
    params = ref.init(KEY)
    toks = jax.random.randint(KEY, (2, 128), 0, cfg.vocab_size)
    want, _ = ref.forward(params, toks, dtype=jnp.float32)

    model = build_model(cfg, impl="pallas")
    assert "repro.kernels.ops" in sys.modules
    assert layers.SDPA_IMPL["pallas"] is sys.modules["repro.kernels.ops"].sdpa_flash

    def no_fallback(*args):
        raise AssertionError("pallas attention fell back to the jnp path")

    monkeypatch.setattr(layers, "_sdpa_jnp", no_fallback)
    got, _ = model.forward(params, toks, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_kernel_and_core_imports_touch_no_backend():
    """Importing the kernels or the live engine initializes no JAX
    backend, so a parent process never claims the chip by importing."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = str(Path(__file__).resolve().parents[1] / "src")
    code = (
        "import repro.kernels.ops, repro.core, repro.core.live\n"
        "from jax._src import xla_bridge\n"
        "print('INIT', xla_bridge.backends_are_initialized())\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, env={**os.environ, "PYTHONPATH": src,
                          "JAX_PLATFORMS": "cpu"},
    )
    assert "INIT False" in r.stdout, r.stdout + r.stderr
