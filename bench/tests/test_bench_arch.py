"""An architecture is a module under ``bench/models`` that a configuration
names by ``arch``: a new one is new files alone, a missing one is refused,
and no other code of the benchmark knows a Llama leaf."""
import glob
import json
import os
import shutil

import pytest

from bench import spec
from bench.tests.conftest import (BENCH, TINY, TINY_LIMITS, add_cell,
                                  copy_bench, drive)


def test_a_new_architecture_is_new_files_only(tmp_path):
    """A copied tree whose only architecture module is ``decoder.py`` (the
    Llama module under another name) runs a cell correct."""
    os.makedirs(tmp_path / "root")
    root = copy_bench(str(tmp_path / "root"))
    models = os.path.join(root, "bench", "models")
    shutil.move(os.path.join(models, "llama.py"),
                os.path.join(models, "decoder.py"))
    with open(os.path.join(BENCH, "configs", "smollm2-360m.json")) as f:
        cfg = dict(json.load(f), **dict(TINY, name="tiny-decoder"),
                   arch="decoder")
    with open(os.path.join(root, "bench", "configs", "tiny-decoder.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(BENCH, "workloads",
                           "smollm2-360m.powersgd.1chip.json")) as f:
        w = json.load(f)
    w["plan"]["bucket_mb"] = 0.0572
    w.update(config="tiny-decoder", chips=1, seq=64, seqs_per_chip=2,
             limits=TINY_LIMITS)
    add_cell(root, "tiny-decoder.powersgd", w)
    sp = spec.Spec(os.path.join(root, "bench"))
    assert sp.arch(sp.config("tiny-decoder")).__file__ == \
        os.path.join(models, "decoder.py")
    res = drive(root, "tiny-decoder.powersgd", cache=str(tmp_path / "cache"))
    assert res["correct"] is True, res["compared"]


@pytest.mark.parametrize("cfg", [{"name": "x", "arch": "no-such-arch"},
                                 {"name": "x"}], ids=["missing", "unnamed"])
def test_a_missing_architecture_is_refused(cfg):
    with pytest.raises(spec.SpecError, match="no-such-arch.py|names no"):
        spec.Spec().arch(cfg)


def test_no_llama_leaf_outside_its_module():
    """The generic code of the benchmark names no leaf path, stack or part
    of a path of the Llama module, and imports no architecture module."""
    llama = spec.Spec().arch({"arch": "llama"})
    names = set()
    for path in llama.shapes(dict(TINY, tie_embeddings=False)):
        stack, _, rest = path.partition("/")
        names |= {path, stack + "/"}
        if stack in llama.STACKS:
            names.add(rest)
    files = [p for p in glob.glob(os.path.join(BENCH, "**", "*.py"),
                                  recursive=True)
             if not p.startswith((os.path.join(BENCH, "models") + os.sep,
                                  os.path.join(BENCH, "tests") + os.sep))]
    assert os.path.join(BENCH, "reference.py") in files
    for p in files:
        with open(p) as f:
            text = f.read()
        assert "bench.models" not in text, p
        for n in sorted(names):
            assert n not in text, (p, n)
