"""The contract between bench/ and a model (bench/models/<model>.py): every
configured model agrees with the program on its header and its parameter
layout, the ResNet-18 model makes the data and weights it always made,
and no file of bench/ outside the models, the ResNet reference and the
tests names anything of one model."""
import bench_paths
import ast
import hashlib
import io
import os
import re
import tokenize

import jax
import numpy as np
import pytest

import harness
import population
import workload

BM = workload.benchmark()
SEED = 4_000_000_123


def configured():
    """(name, sizes, model) of every configuration of BENCHMARK.json."""
    out = []
    for c in BM["configs"]:
        sizes = workload.read_json(os.path.join(workload.ROOT, c["file"]))
        out.append((c["name"], sizes,
                    workload.load_module("models", sizes["model"])))
    return out


@pytest.mark.parametrize("name,sizes,model", configured(),
                         ids=lambda x: x if isinstance(x, str) else "")
def test_model_agrees_with_the_program(name, sizes, model):
    """HEADER is what the program's split keeps per client, and the
    model's weights have the program's layout, shapes and dtype."""
    bench_paths.check_against_program(sizes, model)


# the ResNet-18 model's data and weights of the tiny pfeddst cell at SEED,
# as the benchmark made them before they moved into bench/models/: the
# sum and sum of squares of each float leaf, the sha256 of each integer
# leaf (a change of key, layout or init moves these by far more than
# their tolerance; reordered float sums on another CPU do not)
PINNED = {
    "['images']": (734.7480071875616, 14969.697350221311),
    "['labels']":
        "9a8bb2e82b90da17b80df53d210d1a7c1a83d818f522b905938a64b0676e9952",
    "['params']['head']['b']": (0.0, 0.0),
    "['params']['head']['w']": (0.9303585141897202, 0.38193236653706486),
    "['params']['stages'][0][0]['conv1']": (-40.163761431351304,
                                            774.6390604993596),
    "['params']['stages'][0][0]['conv2']": (-31.218236027285457,
                                            770.4176050529751),
    "['params']['stages'][0][0]['gn1']['bias']": (0.0, 0.0),
    "['params']['stages'][0][0]['gn1']['scale']": (384.0, 384.0),
    "['params']['stages'][0][0]['gn2']['bias']": (0.0, 0.0),
    "['params']['stages'][0][0]['gn2']['scale']": (384.0, 384.0),
    "['params']['stem']['conv']": (14.550603151321411, 762.6857572155977),
    "['params']['stem']['gn']['bias']": (0.0, 0.0),
    "['params']['stem']['gn']['scale']": (384.0, 384.0),
}


def _checksums(tree) -> dict:
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(x)
        if a.dtype.kind in "iub":
            out[jax.tree_util.keystr(path)] = hashlib.sha256(
                a.tobytes()).hexdigest()
        else:
            f = a.astype(np.float64)
            out[jax.tree_util.keystr(path)] = (float(f.sum()),
                                               float((f * f).sum()))
    return out


def test_resnet_data_and_weights_are_pinned():
    cell = bench_paths.tiny_cell("pfeddst.m16")
    model = workload.model(cell)
    keys = population.seed_keys(SEED)
    got = _checksums(model.make_train_data(cell["sizes"], keys["data"]))
    w = harness.weights_fn(cell["sizes"], model)(keys["weights"],
                                                 keys["masks"])
    got.update(_checksums({"params": w["params"]}))
    assert set(got) == set(PINNED)
    for k, want in PINNED.items():
        if isinstance(want, str):
            assert got[k] == want, k
        else:
            assert got[k] == pytest.approx(want, rel=1e-5, abs=1e-9), k


# ---------------------------------------------------------------------------
# no model in the generic code
# ---------------------------------------------------------------------------

MODEL_WORDS = re.compile(r"cnn_|images|labels|resnet")
OWN = ("models" + os.sep, os.path.join("reference", "resnet.py"),
       "tests" + os.sep)


def generic_files() -> list:
    out = []
    for d, _, files in os.walk(bench_paths.BENCH):
        for f in files:
            path = os.path.join(d, f)
            rel = os.path.relpath(path, bench_paths.BENCH)
            if f.endswith(".py") and not rel.startswith(OWN):
                out.append(rel)
    return sorted(out)


def code_words(source: str) -> list:
    """[(line, token)] of the code of a Python file: its tokens without
    comments and without docstrings or other bare string statements."""
    prose = {(n.lineno, n.col_offset) for n in ast.walk(ast.parse(source))
             if isinstance(n, ast.Expr) and isinstance(n.value, ast.Constant)
             and isinstance(n.value.value, str)}
    out = []
    for tok in tokenize.generate_tokens(io.StringIO(source).readline):
        if tok.type == tokenize.COMMENT or (
                tok.type == tokenize.STRING and tok.start in prose):
            continue
        out.append((tok.start[0], tok.string))
    return out


def model_words(source: str) -> list:
    bad = []
    for line, word in code_words(source):
        if MODEL_WORDS.search(word) or word in ('"head"', "'head'"):
            bad.append((line, word))
    return bad


def test_the_scan_sees_code_and_skips_prose():
    src = ('"""A docstring may say resnet and images."""\n'
           '# so may a comment: labels\n'
           'x = data["images"]\n'
           'h = {k: v for k, v in p.items() if k != "head"}\n'
           'def f(cfg):\n'
           '    """cnn_width here is prose."""\n'
           '    return cfg["cnn_width"]\n')
    assert model_words(src) == [(3, '"images"'), (4, '"head"'),
                                (7, '"cnn_width"')]


@pytest.mark.parametrize("rel", generic_files())
def test_generic_code_names_no_model(rel):
    with open(os.path.join(bench_paths.BENCH, rel)) as fh:
        assert model_words(fh.read()) == [], rel
