"""Doc generation + profiling hooks (reference analogs: codegen DocGen
.rst emission; Timer stage tracing upgraded with jax.profiler)."""

import os
import sys

import numpy as np

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools")
)


def test_docgen_emits_rst_for_every_stage_module(tmp_path):
    import docgen

    paths = docgen.generate(str(tmp_path))
    names = {os.path.basename(p) for p in paths}
    assert "index.rst" in names and "models.rst" in names
    # the major stage modules each get a page
    for expected in ("train_classifier.rst", "prep.rst", "image.rst",
                     "dnn_model.rst"):
        assert expected in names, names
    # spot-check content: TrainClassifier page documents its params
    text = (tmp_path / "train_classifier.rst").read_text()
    assert "TrainClassifier" in text
    assert "label_col" in text and "learner" in text.lower()
    # models page lists registered architectures
    mtext = (tmp_path / "models.rst").read_text()
    assert "resnet20_cifar10" in mtext and "transformer_lm" in mtext
    # index references every page
    itext = (tmp_path / "index.rst").read_text()
    assert "train_classifier" in itext


def test_docgen_param_table_shape(tmp_path):
    import docgen

    from mmlspark_tpu.stages.train_classifier import TrainClassifier

    rows = docgen._param_table(TrainClassifier)
    assert any("label_col" in r for r in rows)
    assert any("=" * 5 in r for r in rows)  # rst table rules


def test_trace_profile_writes_trace(tmp_path):
    import jax.numpy as jnp

    from mmlspark_tpu.utils.profiling import (
        FlightRecorder, SpanTracer, trace_profile,
    )

    tracer = SpanTracer(FlightRecorder())
    out = str(tmp_path / "trace")
    with trace_profile(out):
        with tracer.region("test.matmul"):
            (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
    assert [e["name"] for e in tracer.recorder.events()] == ["test.matmul"]
    found = [
        f for root, _, files in os.walk(out) for f in files
        if f.endswith((".pb", ".json.gz", ".trace.json.gz"))
    ]
    assert found, f"no trace artifacts under {out}"


def test_timer_profile_dir(tmp_path):
    from mmlspark_tpu.data.dataset import Dataset
    from mmlspark_tpu.stages.prep import SelectColumns, Timer

    ds = Dataset({"a": np.arange(4.0), "b": np.arange(4.0)})
    out_dir = str(tmp_path / "timer-trace")
    timer = Timer(stage=SelectColumns(cols=["a"]), profile_dir=out_dir)
    out = timer.transform(ds)
    assert out.columns == ["a"]
    assert timer.records and timer.records[0]["seconds"] >= 0
    assert os.path.isdir(out_dir) and os.listdir(out_dir)


def test_docgen_html_rendering(tmp_path):
    """The static HTML assembly (sphinx stand-in): tables become real
    <table> markup and toctree entries become links."""
    import tools.docgen as docgen

    rst_dir = str(tmp_path / "api")
    html_dir = str(tmp_path / "html")
    docgen.generate(rst_dir)
    written = docgen.render_html(rst_dir, html_dir)
    assert len(written) > 10
    with open(os.path.join(html_dir, "dnn_learner.html")) as f:
        page = f.read()
    assert "<table><tr><th>param</th>" in page
    assert "batch_size" in page and "<h2>DNNLearner</h2>" in page
    with open(os.path.join(html_dir, "index.html")) as f:
        index = f.read()
    assert "<a href='dnn_learner.html'>" in index
