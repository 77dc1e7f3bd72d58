"""The step's named scopes: each layer of a server update owns device ops.

The train step names five layers with ``jax.named_scope``: ``fl_client``,
``fl_uplink_codec``, ``fl_downlink_codec``, ``fl_server`` and
``fl_optimizer``. XLA keeps the scopes in each instruction's ``op_name``
metadata, which the profiler shows as the op's name stack. An op belongs to
the innermost scope of its stack. The tests compile small steps and read
that metadata."""

import re
from collections import Counter

import jax
import pytest

from repro.core.fedlite import TrainState, make_train_step
from repro.core.quantizer import PQConfig
from repro.optim import adam

SCOPES = ("fl_client", "fl_uplink_codec", "fl_downlink_codec", "fl_server",
          "fl_optimizer")
_SCOPE = re.compile(r"\b(" + "|".join(SCOPES) + r")\b")
_OP = re.compile(r"^\s*(?:ROOT )?%?(\S+) = .*?\b([a-z][\w-]*)\(.*"
                 r'op_name="([^"]*)"')


def owners(hlo_text):
    """(instruction, opcode, innermost scope or "") per instruction of the
    compiled module that carries an ``op_name``."""
    out = []
    for line in hlo_text.splitlines():
        m = _OP.match(line)
        if m:
            found = _SCOPE.findall(m.group(3))
            out.append((m.group(1), m.group(2), found[-1] if found else ""))
    return out


def test_innermost_rule_on_a_custom_vjp_stack():
    stack = ("jit(loss)/transpose(jvp(fl_uplink_codec))/fl_downlink_codec/"
             "jit(sort)/sort")
    assert _SCOPE.findall(stack)[-1] == "fl_downlink_codec"


@pytest.fixture(scope="module")
def femnist_ops():
    from repro.data.synthetic import make_federated_image_data
    from repro.federated import FederatedTrainer
    from repro.models.paper_models import FemnistCNN
    from repro.optim import sgd
    data = make_federated_image_data(num_clients=4, seed=0)
    model = FemnistCNN(pq=PQConfig(num_subvectors=288, num_clusters=4,
                                   kmeans_iters=2), lam=1e-4, client_batch=4)
    tr = FederatedTrainer(model, sgd(0.03), data, cohort=2, client_batch=4,
                          downlink_compressor="chain:topk(k=0.1)"
                                              "+scalarq(bits=8)")
    key = jax.random.PRNGKey(0)
    parts = [tr.client_batch_for(c, key) for c in (0, 1)]
    lowered = tr.executor.lower(tr.init_state(key), parts)
    return owners(lowered.compile().as_text())


@pytest.fixture(scope="module")
def lm_ops():
    from repro.configs.base import get_arch
    from repro.core.compressors import make_compressor
    from repro.data.synthetic import make_lm_batch
    from repro.models.transformer import TransformerLM
    cfg = get_arch("starcoder2_3b", smoke=True)
    pq = PQConfig(num_subvectors=cfg.d_model // 8, num_clusters=4,
                  kmeans_iters=2)
    model = TransformerLM(cfg, pq=pq, lam=1e-4,
                          downlink_compressor=make_compressor(
                              "scalarq(bits=8)"))
    opt = adam(1e-4)
    state = TrainState.create(model.init(jax.random.PRNGKey(0)), opt)
    batch = make_lm_batch(jax.random.PRNGKey(1), 2, 32, cfg.vocab_size)
    step = make_train_step(model, opt, donate=False)
    return owners(step.lower(state, batch).compile().as_text())


@pytest.mark.parametrize("model", ["femnist", "lm"])
def test_every_scope_owns_ops(model, femnist_ops, lm_ops):
    ops = femnist_ops if model == "femnist" else lm_ops
    counts = Counter(scope for _, _, scope in ops)
    assert all(counts[s] > 0 for s in SCOPES), counts


def test_downlink_topk_sort_belongs_to_the_downlink_codec(femnist_ops):
    sorts = [scope for _, opcode, scope in femnist_ops if opcode == "sort"]
    assert sorts and set(sorts) == {"fl_downlink_codec"}


def test_cached_step_keeps_its_own_scopes(tmp_path):
    """With the persistent compilation cache on, a program that differs from
    a cached one only in its scopes is compiled anew, not served the cached
    executable, whose ops would carry the other program's names."""
    import repro.core.fedlite  # noqa: F401  (keeps metadata in the key)
    from jax.experimental.compilation_cache import compilation_cache as cc
    flags = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in flags}
    for k, v in zip(flags, (True, str(tmp_path), 0, 0)):
        jax.config.update(k, v)
    cc.reset_cache()

    def scoped(scope):
        def f(x):
            with jax.named_scope(scope):
                return jax.numpy.sin(x) * 2
        return f

    try:
        x = jax.numpy.ones(8)
        jax.jit(scoped("fl_client")).lower(x).compile()
        text = jax.jit(scoped("fl_server")).lower(x).compile().as_text()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert "fl_server" in text and "fl_client" not in text
