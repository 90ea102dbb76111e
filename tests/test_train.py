"""Train-loop behaviour: resume bit-exactness, NaN guard, grad compression,
the step's named scopes and the loop's host spans."""
import glob
import os
import shutil
import tempfile
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.synthetic import CTRSpec, SyntheticCTR
from repro.embeddings.table import FieldSpec
from repro.models.dlrm import DLRMConfig
from repro.train.compression import (int8_compress, int8_decompress,
                                     rowsparse_compress, rowsparse_decompress)
from repro.train import loop
from repro.train.loop import Trainer
from repro.train.optimizer import adam, warmup_cosine
from repro.zoo import dlrm_builder


def _tiny_setup(lam: float = 0.0):
    spec = CTRSpec(field_vocabs=(300, 200), batch_size=256, seed=0)
    ds = SyntheticCTR(spec)
    fields = tuple(FieldSpec(f"f{i}", v) for i, v in enumerate(spec.field_vocabs))
    base = DLRMConfig(fields=fields, d_embed=8, mlp_hidden=(16,), backbone="dnn")
    return ds, dlrm_builder(base, ds.expected_frequencies(), lam=lam)


def _mpe_trainer():
    """A tiny DLRM in its MPE search phase, as the benchmark's cell runs."""
    ds, build = _tiny_setup(lam=1e-5)
    b = build(jax.random.PRNGKey(0), "mpe_search", {})
    return ds, Trainer(b["loss_fn"], b["params"], b["buffers"], b["state"],
                       adam(1e-3))


def test_checkpoint_resume_bit_exact():
    ds, build = _tiny_setup()
    d = tempfile.mkdtemp()
    try:
        b = build(jax.random.PRNGKey(0), "plain", {})
        tr = Trainer(b["loss_fn"], b["params"], b["buffers"], b["state"],
                     adam(1e-3), ckpt_dir=d, ckpt_every=10)
        tr.run(lambda s: ds.batch(s), 20, log_every=0)

        b2 = build(jax.random.PRNGKey(0), "plain", {})
        tr2 = Trainer(b2["loss_fn"], b2["params"], b2["buffers"], b2["state"],
                      adam(1e-3), ckpt_dir=d, ckpt_every=10)
        assert tr2.restore() and tr2.step == 20
        tr2.run(lambda s: ds.batch(s), 30, log_every=0)

        b3 = build(jax.random.PRNGKey(0), "plain", {})
        tr3 = Trainer(b3["loss_fn"], b3["params"], b3["buffers"], b3["state"],
                      adam(1e-3))
        tr3.run(lambda s: ds.batch(s), 30, log_every=0)
        for a, c in zip(jax.tree.leaves(tr2.params), jax.tree.leaves(tr3.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_nan_guard_skips_update():
    ds, build = _tiny_setup()
    b = build(jax.random.PRNGKey(0), "plain", {})

    def loss_fn(params, buffers, state, batch, *, step=None):
        loss, aux = b["loss_fn"](params, buffers, state, batch, step=step)
        # poison the loss via the batch's nan flag
        return loss + batch["nan"], aux

    tr = Trainer(loss_fn, b["params"], b["buffers"], b["state"], adam(1e-3))

    def data_fn(step):
        d = ds.batch(step)
        d["nan"] = np.float32("nan") if step in (0, 2) else np.float32(0.0)
        return d

    def snapshot():
        return jax.tree.map(lambda x: np.asarray(x).copy(),
                            (tr.params, tr.carry["opt"]))

    def assert_kept(before):
        after = snapshot()
        assert jax.tree.structure(after) == jax.tree.structure(before)
        for a, c in zip(jax.tree.leaves(after), jax.tree.leaves(before)):
            np.testing.assert_array_equal(a, c)

    # the first step is poisoned: the moments are zero and the bias
    # correction reads step 1, so a skip must not divide 0 by 0
    init = snapshot()
    tr.run(data_fn, 1, log_every=0)
    assert_kept(init)
    assert int(init[1]["step"]) == 0

    tr.run(data_fn, 2, log_every=0)  # a clean step, so the moments are set
    before = snapshot()
    assert int(before[1]["step"]) == 1
    assert np.abs(jax.tree.leaves(before[1]["mu"])[0]).max() > 0

    tr.run(data_fn, 3, log_every=0)  # poisoned again, once the moments are set
    assert_kept(before)

    tr.run(data_fn, 4, log_every=0)  # clean step applies
    after2 = np.asarray(jax.tree.leaves(tr.params)[0])
    assert np.abs(after2 - jax.tree.leaves(before[0])[0]).max() > 0
    assert int(tr.carry["opt"]["step"]) == 2


def test_int8_error_feedback_telescopes(rng):
    """Σ decompressed_t -> Σ g_t (bias cancels through the residual)."""
    g_true = jnp.asarray(rng.normal(0, 1, (50, 64)), jnp.float32)
    err = jnp.zeros((64,))
    total = jnp.zeros((64,))
    for t in range(50):
        q, s, err = int8_compress(g_true[t], err)
        total = total + int8_decompress(q, s)
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(jnp.sum(g_true, 0)),
                               rtol=0, atol=np.abs(np.asarray(g_true)).max() / 60)


def test_rowsparse_roundtrip(rng):
    g = jnp.zeros((100, 8)).at[jnp.asarray([3, 50, 99])].set(1.5)
    idx, vals = rowsparse_compress(g, jnp.asarray([3, 50, 99]))
    back = rowsparse_decompress(100, idx, vals)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(g))


def test_lr_schedule():
    fn = warmup_cosine(1e-3, warmup=10, total=100)
    assert float(fn(jnp.asarray(0))) == 0.0
    assert abs(float(fn(jnp.asarray(10))) - 1e-3) < 1e-9
    assert float(fn(jnp.asarray(100))) < 1e-5


@pytest.fixture(scope="module")
def step_text():
    ds, tr = _mpe_trainer()
    return tr.compiled_step(ds.batch(0)).as_text()


@pytest.mark.parametrize("scope", [
    "jvp(embed_gather)", "transpose(jvp(embed_gather))", "jvp(embed_quantize)",
    "transpose(jvp(embed_quantize))", "jvp(tower)", "transpose(jvp(tower))",
    "clip", "update"])
def test_compiled_step_names_every_scope(step_text, scope):
    """The executable ``run`` calls tags its ops with the step's parts; the
    transpose of the gather is the table gradient."""
    assert f'op_name="jit(train_step)/{scope}/' in step_text


def test_run_profile_holds_the_trainer_spans(tmp_path):
    from jax.profiler import ProfileData
    ds, tr = _mpe_trainer()
    tr.run(lambda s: ds.batch(s), 1, log_every=0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr.run(lambda s: ds.batch(s), 4, log_every=0)
        jax.block_until_ready(tr.carry)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(path)
    names = [e.name for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events]
    for span in ("trainer.step", "trainer.data", "trainer.stage",
                 "trainer.dispatch"):
        assert names.count(span) == 3, span


def test_run_logs_ms_per_step_of_this_run(monkeypatch):
    """ms/step divides this run's time by this run's steps, not by every
    step since the trainer was made."""
    ds, tr = _mpe_trainer()
    tr.run(lambda s: ds.batch(s), 10, log_every=0)
    ticks = iter(range(100))
    monkeypatch.setattr(loop, "time", types.SimpleNamespace(
        perf_counter=lambda: float(next(ticks))))
    lines = []
    tr.run(lambda s: ds.batch(s), 12, log_every=2, log_fn=lines.append)
    # one tick to start, one at the log point after two steps: 0.5 s a step
    assert lines[-1].endswith("(500.0 ms/step)")
    assert tr.history[-1]["wall_s"] == 1.0
