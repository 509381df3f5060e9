"""The program names its own work for a trace (``repro.tracing``): every
compiled training step carries its ``db.*`` device scopes in the HLO
``op_name``s, with every matrix product and kernel call under one, and the
host loops open their ``db.*`` spans in order."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs.base import DBConfig, ModelConfig, TrainConfig
from repro.core import DiffusionBlocksModel
from repro.core.training import GuardConfig, make_db_train_step, train_db
from repro.parallel import BlockParallelTrainer

CFG = ModelConfig(name="t", family="dense", n_layers=4, d_model=32,
                  n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=17)
TRAIN_SCOPES = {tracing.BLOCK_VIEW, tracing.NOISE, tracing.LAYERS,
                tracing.ATTN, tracing.MLP, tracing.ADALN, tracing.READOUT_CE,
                tracing.OPTIMIZER}


def hlo_instructions(text):
    """(name, opcode, op_name or None) of every instruction in HLO text."""
    out = []
    for line in text.splitlines():
        m = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$", line)
        if not m:
            continue
        rest = m.group(2)
        # the opcode is the first lower-case word followed by "(" (layout
        # tags in shapes are upper case)
        opc = re.search(r"\s([a-z][\w\-]*)\(", " " + rest)
        op = re.search(r'op_name="([^"]*)"', rest)
        out.append((m.group(1), opc.group(1) if opc else "?",
                    op.group(1) if op else None))
    return out


def scopes_in(op_name):
    return re.findall(r"(?<![\w.])db\.[a-z_]+", op_name or "")


def check_scoped(text, expect):
    ins = hlo_instructions(text)
    seen = {s for _, _, o in ins for s in scopes_in(o)}
    assert expect <= seen, sorted(expect - seen)
    bare = [(n, c, o) for n, c, o in ins
            if c in ("dot", "custom-call", "convolution") and not scopes_in(o)]
    assert not bare, bare[:5]
    assert any(c == "dot" for _, c, _ in ins)


@pytest.fixture(scope="module")
def dbm():
    return DiffusionBlocksModel(CFG, DBConfig(num_blocks=2,
                                              overlap_gamma=0.1))


@pytest.fixture(scope="module")
def params(dbm):
    return dbm.init(jax.random.PRNGKey(0))


def tcfg():
    return TrainConfig(steps=2, batch_size=2, seq_len=16, log_every=0)


def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 17)


@pytest.mark.parametrize("impl", ["kernels", "auto"])
def test_block_step_carries_every_training_scope(dbm, params, impl):
    init, step = make_db_train_step(dbm, 0, tcfg(), impl=impl,
                                    precision="bf16")
    text = step.lower(params, init(params), tokens(),
                      jax.random.PRNGKey(2)).compile().as_text()
    check_scoped(text, TRAIN_SCOPES)
    # the backward keeps its forward's scope
    assert re.search(r'op_name="[^"]*transpose\(jvp\(db\.layers\)\)[^"]*'
                     r'db\.attn', text)


def test_guarded_block_step_names_the_guard(dbm, params):
    init, step = make_db_train_step(dbm, 1, tcfg(), precision="bf16",
                                    guard=GuardConfig())
    text = step.lower(params, init(params), jnp.float32(-1.0), tokens(),
                      jax.random.PRNGKey(2)).compile().as_text()
    check_scoped(text, TRAIN_SCOPES | {tracing.GUARD})


def _parallel_args(tr, state):
    B = tr.B
    return (state.stacks, state.stack_opt, state.periph, state.periph_opt,
            tokens(), jax.random.split(jax.random.PRNGKey(3), B),
            tr.qranges, tr.block_ids, jnp.ones((B,), jnp.float32),
            jnp.ones((B,), jnp.float32), tr.guard_ewma, jnp.asarray(True))


def test_parallel_step_carries_every_engine_scope(dbm, params):
    tr = BlockParallelTrainer(dbm, tcfg(), devices=jax.devices()[:1],
                              precision="bf16")
    state = tr.init_state(params)
    text = tr._step_fn.lower(*_parallel_args(tr, state)).compile().as_text()
    check_scoped(text, (TRAIN_SCOPES - {tracing.BLOCK_VIEW})
                 | {tracing.PSUM, tracing.GUARD})


@contextlib.contextmanager
def recorded_spans(monkeypatch):
    names = []

    def span(name):
        names.append(name)
        return contextlib.nullcontext()
    monkeypatch.setattr(tracing, "span", span)
    yield names


def test_parallel_step_opens_its_host_spans_in_order(dbm, params,
                                                     monkeypatch):
    tr = BlockParallelTrainer(dbm, tcfg(), devices=jax.devices()[:1])
    state = tr.init_state(params)
    with recorded_spans(monkeypatch) as names:
        state, losses, _ = tr.step(state, tokens(),
                                   jax.random.split(jax.random.PRNGKey(4),
                                                    tr.B))
        tr.step(state, tokens(), jax.random.split(jax.random.PRNGKey(5),
                                                  tr.B))
    assert names == [tracing.PLACE, tracing.DISPATCH, tracing.GUARD_SYNC] * 2
    assert np.all(np.isfinite(np.asarray(losses)))


def test_sequential_loop_opens_its_host_spans_in_order(dbm, params,
                                                       monkeypatch):
    def data():
        while True:
            yield tokens()
    with recorded_spans(monkeypatch) as names:
        _, hist = train_db(dbm, tcfg(), data(), jax.random.PRNGKey(6),
                           params=params)
    assert len(hist) == 2
    assert names == [tracing.BATCH, tracing.DISPATCH,
                     tracing.LOSS_READBACK] * 2


def test_serving_programs_and_batcher_are_named(monkeypatch):
    from repro.launch.serve import ContinuousBatcher
    cfg = ModelConfig(name="tiny-decode", family="dense", n_layers=4,
                      d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
                      vocab_size=32)
    dbm = DiffusionBlocksModel(cfg, DBConfig(num_blocks=2,
                                             overlap_gamma=0.1))
    params = dbm.init(jax.random.PRNGKey(0))
    cb = ContinuousBatcher(dbm, params, num_slots=2, max_prompt=8,
                           max_len=16, seg_len=4, page_size=4)
    common = (cb.params, cb.kv, jnp.asarray(cb.table),
              jnp.asarray(cb.lengths), jnp.asarray(cb.prompt_buf),
              jnp.asarray(cb.plens))
    serve = cb.eng._serve.lower(
        *common, jnp.asarray(cb.stop_at), jnp.asarray(cb.active),
        jax.random.PRNGKey(1), jnp.asarray(cb.cond_lengths),
        n=cb.seg_len).compile().as_text()
    seen = {s for _, _, o in hlo_instructions(serve) for s in scopes_in(o)}
    assert {tracing.PROBE, tracing.COMMIT, tracing.SAMPLE} <= seen, seen
    chunk = cb.eng._prefill_chunk1.lower(
        *common, jnp.asarray(cb.cond_lengths)).compile().as_text()
    assert tracing.COMMIT in {s for _, _, o in hlo_instructions(chunk)
                              for s in scopes_in(o)}
    with recorded_spans(monkeypatch) as names:
        cb.submit(np.arange(5) % 32, max_new=3)
        done = cb.run(jax.random.PRNGKey(2))
    assert len(done) == 1 and len(done[0].out) == 3
    assert names[0] == tracing.ADMIT and tracing.RETIRE in names
    assert set(names) <= {tracing.ADMIT, tracing.COW, tracing.RETIRE}


def test_scope_and_span_are_jax_objects():
    assert isinstance(tracing.scope(tracing.ATTN),
                      type(jax.named_scope("x")))
    with tracing.span(tracing.PLACE):
        pass
    assert all(n.startswith("db.") for k, n in vars(tracing).items()
               if k.isupper())
