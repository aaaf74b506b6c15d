"""Packaging layer: version, dist assemblies, example driver.

Reference role: tez-dist assemblies
(tez-dist/src/main/assembly/{tez-dist,tez-dist-minimal}.xml) and
ExampleDriver (tez-examples/.../ExampleDriver.java:33).
"""
import sys
import tarfile

import tez_tpu
from tez_tpu.examples import driver
from tez_tpu.tools import dist


def test_version_exported():
    assert tez_tpu.__version__.count(".") == 2


def test_dist_full_and_minimal(tmp_path):
    full = dist.build(minimal=False, out_dir=str(tmp_path))
    minimal = dist.build(minimal=True, out_dir=str(tmp_path))
    with tarfile.open(full) as tf:
        names = tf.getnames()
    root = names[0].split("/")[0]
    assert any(n.endswith("tez_tpu/examples/driver.py") for n in names)
    assert not any(n.endswith("/bench.py") for n in names)
    assert f"{root}/README.md" in names
    assert f"{root}/pyproject.toml" in names
    assert f"{root}/docs/device_pipeline.md" in names
    assert any(n.endswith("native/ragged.cpp") for n in names)
    # every source the Makefile needs must ship, or make -C native fails
    assert any(n.endswith("native/shuffle_server.cpp") for n in names)
    assert any(n.endswith("native/Makefile") for n in names)
    assert f"{root}/MANIFEST" in names
    with tarfile.open(minimal) as tf:
        min_names = tf.getnames()
    assert not any("/examples/" in n or "/models/" in n for n in min_names)
    # tools stay in minimal (AM web imports them at request time)
    assert any("/tools/analyzers.py" in n for n in min_names)
    assert any(n.endswith("tez_tpu/am/app_master.py") for n in min_names)
    assert any(n.endswith("native/ragged.cpp") for n in min_names)
    assert any(n.endswith("native/shuffle_server.cpp") for n in min_names)
    assert len(min_names) < len(names)


def test_example_driver_usage(capsys, monkeypatch):
    monkeypatch.setattr(sys, "argv", ["tez-examples"])
    assert driver.main() == 2
    out = capsys.readouterr().out
    for name in ("wordcount", "orderedwordcount", "mrr", "sortmergejoin",
                 "hashjoin"):
        assert name in out


def test_example_driver_runs_wordcount(tmp_path, capsys, monkeypatch):
    corpus = tmp_path / "in.txt"
    corpus.write_text("a b a c a b\n")
    out_dir = str(tmp_path / "out")
    monkeypatch.setattr(
        sys, "argv", ["tez-examples", "wordcount", str(corpus), out_dir])
    assert driver.main() == 0
    assert "SUCCEEDED" in capsys.readouterr().out


def test_cartesian_product_example(tmp_path, capsys, monkeypatch):
    left = tmp_path / "l.txt"; left.write_text("a b\n")
    right = tmp_path / "r.txt"; right.write_text("x y z\n")
    out = str(tmp_path / "out")
    monkeypatch.setattr(sys, "argv", ["tez-examples", "cartesianproduct",
                                      str(left), str(right), out])
    assert driver.main() == 0
    import os
    pairs = set()
    for f in os.listdir(out):
        if f.startswith("part-"):
            for line in open(os.path.join(out, f)):
                pairs.add(line.split("\t")[0])
    assert pairs == {f"{a}|{b}" for a in "ab" for b in "xyz"}


def test_simple_session_example(tmp_path, capsys, monkeypatch):
    files = []
    for i in range(2):
        p = tmp_path / f"in{i}.txt"
        p.write_text(f"w{i} w{i} other\n")
        files.append(str(p))
    out = str(tmp_path / "out")
    monkeypatch.setattr(sys, "argv", ["tez-examples", "simplesessionexample",
                                      *files, out])
    assert driver.main() == 0
    import os
    assert sorted(os.listdir(out)) == ["dag0", "dag1"]
