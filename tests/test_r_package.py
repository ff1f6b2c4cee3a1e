"""R package checks without an R runtime.

The image ships no R interpreter, so the R surface is verified
mechanically (SURVEY §4 fake-backend discipline applied to a language
runtime): a tokenizer-level lint (scripts/r_lint.py) proves every file
lexes with balanced delimiters, and the extracted top-level function
signatures are compared argument-by-argument against the REFERENCE
R-package's signatures (R-package/R/*.R) — the strongest parity check
available short of executing R.  The CLI task the R binding leans on
(`task=dump_model`) is exercised for real.
"""
import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))
from r_lint import RLintError, lint_file, tokenize, check_balance  # noqa: E402

OUR_R = sorted(glob.glob(os.path.join(REPO, "R-package", "R", "*.R")))
REF_R = sorted(glob.glob("/root/reference/R-package/R/*.R"))


def _functions(paths):
    fns = {}
    for path in paths:
        for fn in lint_file(path):
            fns[fn.name] = fn
    return fns


@pytest.fixture(scope="module")
def our_fns():
    return _functions(OUR_R)


@pytest.fixture(scope="module")
def ref_fns():
    if not REF_R:
        pytest.skip("reference R package not available")
    return _functions(REF_R)


@pytest.mark.parametrize("path", OUR_R, ids=os.path.basename)
def test_r_file_lints(path):
    fns = lint_file(path)   # raises RLintError on lexical problems
    assert isinstance(fns, list)


@pytest.mark.parametrize("path", REF_R, ids=os.path.basename)
def test_linter_accepts_reference_files(path):
    """The linter must parse real-world R (all 21 reference files), or a
    pass on our files would mean nothing."""
    lint_file(path)


@pytest.mark.parametrize("snippet,err", [
    ('x <- "unterminated\n', "unterminated"),
    ("f <- function(a, b { a + b }", "unclosed"),
    ("x <- foo(bar[1)]", "mismatched"),
    ("y <- x %in c(1, 2)\n", "%op%"),
    ("f <- function() { if (x) { y } ", "unclosed"),
])
def test_linter_rejects_broken_r(snippet, err):
    with pytest.raises(RLintError) as ei:
        check_balance(tokenize(snippet, "<t>"), "<t>")
    assert err in str(ei.value)


# entry points whose argument lists must match the reference's exactly
# (ours may append trailing optional args; prefix must agree in order)
PARITY = [
    "lightgbm", "lgb.Dataset", "lgb.Dataset.create.valid",
    "lgb.Dataset.construct", "lgb.Dataset.set.categorical",
    "lgb.Dataset.set.reference", "lgb.Dataset.save",
    "lgb.train", "lgb.cv", "lgb.load", "lgb.save", "lgb.dump",
    "lgb.get.eval.result", "lgb.importance", "lgb.model.dt.tree",
    "lgb.plot.importance", "lgb.unloader", "lgb.interprete",
    "lgb.plot.interpretation", "lgb.prepare", "lgb.prepare2",
    "lgb.prepare_rules", "lgb.prepare_rules2",
    "predict.lgb.Booster", "slice.lgb.Dataset",
    "getinfo.lgb.Dataset", "setinfo.lgb.Dataset",
    "dim.lgb.Dataset", "dimnames.lgb.Dataset",
    "saveRDS.lgb.Booster", "readRDS.lgb.Booster",
]


def test_required_entry_points_exist(our_fns):
    missing = [n for n in PARITY if n not in our_fns]
    assert not missing, f"R entry points missing: {missing}"


def test_signatures_match_reference(our_fns, ref_fns):
    diffs = []
    for name in PARITY:
        if name not in ref_fns:
            continue    # our extension (reference defines it inside R6)
        ref_args = list(ref_fns[name].args)
        our_args = list(our_fns[name].args)
        if our_args[:len(ref_args)] != ref_args:
            diffs.append(f"{name}: ours{our_args} vs ref{ref_args}")
    assert not diffs, "signature drift vs reference:\n" + "\n".join(diffs)


def test_namespace_exports_are_defined(our_fns):
    ns = os.path.join(REPO, "R-package", "NAMESPACE")
    exported = []
    with open(ns) as f:
        for line in f:
            line = line.strip()
            if line.startswith("export("):
                exported.append(line[len("export("):-1])
            elif line.startswith("S3method("):
                generic, cls = line[len("S3method("):-1].split(", ")
                exported.append(f"{generic.strip(chr(34))}.{cls}")
    missing = [e for e in exported if e not in our_fns]
    assert not missing, f"NAMESPACE exports undefined functions: {missing}"


def test_r_eval_log_parsing_contract(tmp_path):
    """The R binding parses record_evals and best_iter out of the CLI's
    stderr/stdout with fixed regexes; run a REAL CLI training with a
    validation set + early stopping and assert those exact patterns
    (read out of the R sources, not re-typed here) match the live log
    — the contract that would silently rot if the log format drifted."""
    import re
    utils_r = open(os.path.join(REPO, "R-package", "R", "utils.R")).read()
    train_r = open(os.path.join(REPO, "R-package", "R", "lgb.train.R")).read()

    def r_patterns(src):
        # R string literal -> regex: \\ is a backslash, \t a tab
        return [p.replace("\\\\", "\\")
                for p in re.findall(r'regexec\("((?:[^"\\]|\\.)*)"', src)]

    iter_pat, part_pat = r_patterns(utils_r)
    best_pat = [p for p in r_patterns(train_r) if "best iteration" in p]
    assert best_pat, "best-iteration pattern not found in lgb.train.R"
    best_pat = best_pat[0]

    rng = np.random.RandomState(0)
    X = rng.randn(1200, 5)
    y = (X[:, 0] + 0.2 * rng.randn(1200) > 0).astype(np.float64)
    np.savetxt(tmp_path / "tr.tsv", np.column_stack([y, X])[:900],
               delimiter="\t")
    np.savetxt(tmp_path / "va.tsv", np.column_stack([y, X])[900:],
               delimiter="\t")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.cli", "task=train",
         f"data={tmp_path / 'tr.tsv'}", f"valid_data={tmp_path / 'va.tsv'}",
         "objective=binary", "metric=auc,binary_logloss", "num_trees=30",
         "num_leaves=7", "early_stopping_round=3", "verbose=1",
         f"output_model={tmp_path / 'm.txt'}"],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-1000:]
    log = (r.stdout + r.stderr).splitlines()

    eval_lines = [ln for ln in log if re.search(iter_pat, ln)]
    assert len(eval_lines) >= 3, "no eval lines matched the R iter pattern"
    parsed = 0
    for ln in eval_lines:
        body = re.search(iter_pat, ln)
        assert body.group(1).isdigit()
        for part in body.group(2).split("\t"):
            pm = re.match(part_pat, part)
            assert pm, f"R part pattern failed on {part!r}"
            assert pm.group(2) in ("auc", "binary_logloss")
            float(pm.group(3))
            parsed += 1
    assert parsed >= 6
    best = [re.search(best_pat, ln) for ln in log]
    best = [m for m in best if m]
    assert best, "early stopping fired but the R best-iter pattern missed it"
    assert int(best[-1].group(1)) >= 1


def test_cli_dump_model_task(tmp_path):
    """The R package's lgb.dump rides `task=dump_model`; prove the CLI
    produces parseable JSON with the documented top-level keys."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.randn(300, 4)
    y = (X[:, 0] - X[:, 1] > 0).astype(np.float64)
    bst = lgb.train({"objective": "binary", "verbose": -1, "num_leaves": 7},
                    lgb.Dataset(X, label=y), num_boost_round=3)
    model_file = tmp_path / "m.txt"
    bst.save_model(str(model_file))
    out_file = tmp_path / "m.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.cli", "task=dump_model",
         f"input_model={model_file}", f"convert_model={out_file}"],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-1000:]
    dump = json.loads(out_file.read_text())
    assert dump["num_class"] == 1
    assert len(dump["tree_info"]) == 3
