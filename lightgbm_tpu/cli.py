"""Command-line application — the reference CLI's analogue.

``lightgbm-tpu config=train.conf [key=value ...]`` mirrors
``src/application/application.cpp`` + ``src/main.cpp``: k=v args merged over a
config file (CLI wins), task dispatch train / predict / convert_model, data
loaded from text files with ``.weight``/``.query`` side files, models in the
reference text format.
"""
from __future__ import annotations

import sys
from typing import Dict, List

import numpy as np

from .basic import Booster, Dataset
from .config import (Config, canonicalize_params, config_from_params,
                     parse_config_file)
from .engine import train as train_fn
from .utils import log
from .utils.cache import enable_persistent_cache


def parse_cli(argv: List[str]) -> Dict[str, str]:
    params: Dict[str, str] = {}
    for arg in argv:
        if "=" not in arg:
            log.warning("Unknown CLI argument %s (expected key=value)", arg)
            continue
        k, v = arg.split("=", 1)
        params[k.strip()] = v.strip()
    if "config" in params or "config_file" in params:
        path = params.pop("config", None) or params.pop("config_file")
        file_params = parse_config_file(path)
        for k, v in file_params.items():
            params.setdefault(k, v)  # CLI args win (application.cpp:48-104)
    return params


def run_train(cfg: Config, params: Dict[str, str]) -> None:
    if not cfg.data:
        log.fatal("No training data specified (data=...)")
    dtrain = Dataset(cfg.data, params=params)
    valid_sets, valid_names = [], []
    for i, vpath in enumerate(cfg.valid_data):
        valid_sets.append(dtrain.create_valid(vpath))
        valid_names.append(f"valid_{i + 1}")
    if cfg.is_training_metric:
        valid_sets = [dtrain] + valid_sets
        valid_names = ["training"] + valid_names
    booster = train_fn(dict(params), dtrain,
                       num_boost_round=cfg.num_iterations,
                       valid_sets=valid_sets, valid_names=valid_names,
                       early_stopping_rounds=cfg.early_stopping_round or None,
                       verbose_eval=cfg.output_freq if cfg.verbose >= 1 else False,
                       # snapshot_resume=true: a preempted/killed run is
                       # re-launched with the SAME command line and picks up
                       # from the latest valid checkpoint (docs/ROBUSTNESS.md)
                       resume=cfg.snapshot_resume or None)
    booster.save_model(cfg.output_model)
    log.info("Finished training; model saved to %s", cfg.output_model)


def run_predict(cfg: Config, params: Dict[str, str]) -> None:
    if not cfg.data:
        log.fatal("No prediction data specified (data=...)")
    if not cfg.input_model:
        log.fatal("No model specified (input_model=...)")
    # serving path: native C++ predictor (predictor.hpp analogue) unless a
    # feature it doesn't cover (early stop) is requested
    from . import native
    if native.available() and not cfg.pred_early_stop:
        from .data.parser import load_text_file
        X, _, _ = load_text_file(cfg.data, has_header=cfg.has_header,
                                 label_idx=0)
        pred = native.NativePredictor(model_file=cfg.input_model)
        if cfg.is_predict_leaf_index:
            preds = pred.predict_leaf(X, cfg.num_iteration_predict)
        else:
            preds = pred.predict(X, cfg.num_iteration_predict,
                                 cfg.is_predict_raw_score)
        out = np.asarray(preds).reshape(np.asarray(X).shape[0], -1)
        np.savetxt(cfg.output_result, out, delimiter="\t", fmt="%.18g")
        log.info("Finished prediction (native); results saved to %s",
                 cfg.output_result)
        return
    booster = Booster(model_file=cfg.input_model, params=params)
    preds = booster.predict(cfg.data,
                            num_iteration=cfg.num_iteration_predict,
                            raw_score=cfg.is_predict_raw_score,
                            pred_leaf=cfg.is_predict_leaf_index,
                            pred_early_stop=cfg.pred_early_stop)
    out = np.atleast_2d(np.asarray(preds))
    if out.shape[0] == 1:
        out = out.T
    np.savetxt(cfg.output_result, out, delimiter="\t", fmt="%.18g")
    log.info("Finished prediction; results saved to %s", cfg.output_result)


def run_convert_model(cfg: Config, params: Dict[str, str]) -> None:
    """convert_model task: emit the model as portable, dependency-free C++
    if-else code (gbdt.cpp ModelToIfElse analogue) with the EXACT
    NumericalDecision/CategoricalDecision semantics of tree.h:231-313 —
    all three missing modes, default-left routing, categorical bitsets,
    multiclass tree interleaving.  The generated translation unit exports

        extern "C" void PredictRawAll(const double* fval, double* out);
        double PredictRaw(const double* fval);      // num_class == 1 only

    and is the compiled-model oracle for the conversion-consistency test
    (the reference's tests/cpp_test discipline)."""
    booster = Booster(model_file=cfg.input_model, params=params)
    trees = booster.inner.models
    k = max(booster.inner.num_class, 1)
    lines = ["#include <cmath>", "",
             "// categorical split bitsets (tree.h cat_threshold)"]
    for ti, t in enumerate(trees):
        for node in range(t.num_leaves - 1):
            if t.is_categorical(node):
                bits = ", ".join(f"{int(b)}u" for b in t.cat_bitset(node))
                lines.append(f"static const unsigned int kCat_{ti}_{node}"
                             f"[] = {{{bits}}};")
    lines += [
        "",
        "// CategoricalDecision (tree.h:268-283)",
        "static bool InBitset(const unsigned int* bits, int n, double fval,",
        "                     bool nan_is_missing) {",
        "  if (std::isnan(fval)) {",
        "    if (nan_is_missing) return false;",
        "    fval = 0.0;",
        "  }",
        "  const int v = static_cast<int>(fval);",
        "  if (v < 0) return false;",
        "  const int i1 = v / 32, i2 = v % 32;",
        "  return i1 < n && ((bits[i1] >> i2) & 1u);",
        "}",
        "",
        'extern "C" void PredictRawAll(const double* fval, double* out) {',
        f"  for (int c = 0; c < {k}; ++c) out[c] = 0.0;",
    ]
    for ti, t in enumerate(trees):
        cls = ti % k
        lines.append(f"  // tree {ti} (class {cls})")
        if t.num_leaves <= 1:
            lines.append(f"  out[{cls}] += {t.leaf_value[0]:.17g};")
            continue
        # explicit stack, not recursion — leaf-wise trees can be deeper
        # than the Python recursion limit
        stack = [("node", 0, 1)]
        while stack:
            kind, item, indent = stack.pop()
            if kind == "text":
                lines.append(item)
                continue
            node = item
            pad = "  " * indent
            if node < 0:
                leaf = ~node
                lines.append(f"{pad}out[{cls}] += "
                             f"{t.leaf_value[leaf]:.17g};")
                continue
            f = int(t.split_feature[node])
            if t.is_categorical(node):
                nbits = len(t.cat_bitset(node))
                nan_missing = "true" if t.missing_type(node) == 2 else "false"
                cond = (f"InBitset(kCat_{ti}_{node}, {nbits}, fval[{f}], "
                        f"{nan_missing})")
            else:
                # NumericalDecision (tree.h:231-266): NaN maps to 0.0
                # unless missing_type is NaN; zero-range/NaN missing
                # routes by default_left; otherwise v <= threshold
                thr = float(t.threshold[node])
                mt = t.missing_type(node)
                dl = "true" if t.default_left(node) else "false"
                v = f"(std::isnan(fval[{f}]) ? 0.0 : fval[{f}])"
                if mt == 2:       # NaN is the missing value
                    cond = (f"(std::isnan(fval[{f}]) ? {dl} : "
                            f"(fval[{f}] <= {thr:.17g}))")
                elif mt == 1:     # zero range is the missing value
                    cond = (f"(std::fabs({v}) <= 1e-20 ? {dl} : "
                            f"({v} <= {thr:.17g}))")
                else:             # no missing handling; NaN folds to 0.0
                    cond = f"{v} <= {thr:.17g}"
            lines.append(f"{pad}if ({cond}) {{")
            stack.append(("text", f"{pad}}}", 0))
            stack.append(("node", int(t.right_child[node]), indent + 1))
            stack.append(("text", f"{pad}}} else {{", 0))
            stack.append(("node", int(t.left_child[node]), indent + 1))
    lines.append("}")
    if k == 1:
        lines += ["",
                  'extern "C" double PredictRaw(const double* fval) {',
                  "  double out = 0.0;",
                  "  PredictRawAll(fval, &out);",
                  "  return out;",
                  "}"]
    with open(cfg.convert_model, "w") as f:
        f.write("\n".join(lines) + "\n")
    log.info("Model converted to %s", cfg.convert_model)


def run_dump_model(cfg: Config, params: Dict[str, str]) -> None:
    """dump_model task: write the model as JSON (the C API's
    LGBM_BoosterDumpModel / Python dump_model surface, exposed through
    the CLI so file-transport bindings — the R package — can reach it).
    Output path comes from ``convert_model`` (shared with the C++
    converter task); when not given explicitly it defaults to
    ``<input_model>.json`` rather than the converter's .cpp name."""
    import json
    if not cfg.input_model:
        log.fatal("No model specified (input_model=...)")
    # explicit convert_model= (under any alias) wins even if it equals
    # the converter default; otherwise default to <input_model>.json
    given = "convert_model" in canonicalize_params(params)
    out_path = cfg.convert_model if given else cfg.input_model + ".json"
    booster = Booster(model_file=cfg.input_model, params=params)
    with open(out_path, "w") as f:
        json.dump(booster.dump_model(), f)
    log.info("Model dumped to %s", out_path)


def main(argv: List[str] = None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    params = parse_cli(argv)
    cfg = config_from_params(params)
    log.set_verbosity(cfg.verbose)
    enable_persistent_cache()
    if cfg.num_machines > 1:
        # bring the network layer up before any device work, exactly like
        # the reference CLI (application.cpp:190-224)
        from .parallel.mesh import init_distributed_from_config
        init_distributed_from_config(cfg)
    task = params.get("task", "train")
    if task == "train":
        run_train(cfg, params)
    elif task in ("predict", "prediction", "test"):
        run_predict(cfg, params)
    elif task == "convert_model":
        run_convert_model(cfg, params)
    elif task == "dump_model":
        run_dump_model(cfg, params)
    else:
        log.fatal("Unknown task %s", task)
    return 0


if __name__ == "__main__":
    sys.exit(main())
