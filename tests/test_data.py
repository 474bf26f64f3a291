"""Synthetic few-shot data pipeline."""
import numpy as np
import pytest

from repro.data.synthetic import DATASETS, LABEL_BASE, SEP, lm_batch_stream, make_task


@pytest.mark.parametrize("name", list(DATASETS))
def test_task_structure(name):
    task = make_task(name, vocab=1000, n_queries=8, seed=3)
    spec = DATASETS[name]
    assert task.n_classes == spec["n_classes"]
    # prefix = examples * (body + sep + label + sep)
    assert len(task.prefix) == spec["examples"] * (spec["body_len"] + 3)
    assert len(task.queries) == 8
    for suffix, cls in task.queries:
        assert 0 <= cls < task.n_classes
        assert suffix[-1] == SEP  # ends at the separator before the label
        assert task.label_token(cls) == LABEL_BASE + cls


def test_task_deterministic():
    a = make_task("rte", 500, n_queries=4, seed=7)
    b = make_task("rte", 500, n_queries=4, seed=7)
    np.testing.assert_array_equal(a.prefix, b.prefix)


def test_labels_learnable_signal():
    """Planted class markers appear in example bodies (the signal a tiny
    model can learn for the quality benchmarks)."""
    task = make_task("sst2", 1000, n_queries=4, seed=0)
    markers = {LABEL_BASE + task.n_classes + c for c in range(task.n_classes)}
    assert markers & set(task.prefix.tolist())


def test_lm_batch_stream_shapes():
    stream = lm_batch_stream(vocab=512, batch=4, seq=32, seed=0)
    for _ in range(3):
        batch = next(stream)
        assert batch["tokens"].shape == (4, 32)
        assert batch["labels"].shape == (4, 32)
        # labels are next-token shifted
        assert batch["tokens"].dtype == np.int32
        assert (batch["tokens"] < 512).all() and (batch["tokens"] >= 0).all()


def test_stream_is_next_token_prediction():
    stream = lm_batch_stream(vocab=512, batch=2, seq=16, seed=1)
    b1 = next(stream)
    # within one document chunk, labels[i] == tokens[i+1]
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])


def test_task_same_in_every_process():
    """The served prefix must not depend on the per-process str-hash salt."""
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = ("from repro.data.synthetic import make_task; "
            "t = make_task('rte', 1000, n_queries=2, prefix_len=300); "
            "print(t.prefix.tolist(), [q.tolist() for q, _ in t.queries])")
    outs = []
    for salt in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=salt, PYTHONPATH=src)
        outs.append(subprocess.run([sys.executable, "-c", code], env=env,
                                   capture_output=True, text=True,
                                   check=True, timeout=120).stdout)
    assert outs[0] == outs[1]
    assert len(make_task("rte", 1000, prefix_len=300).prefix) == 300
