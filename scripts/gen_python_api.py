"""Generate docs/Python-API.md from the live package (run from repo
root).  Mirrors the reference's docs/Python-API.md section layout."""
import inspect
import io
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import lightgbm_tpu as lgb  # noqa: E402

SECTIONS = [
    ("Data structure API", ["Dataset", "Booster"]),
    ("Training API", ["train", "cv"]),
    ("Scikit-learn API", ["LGBMModel", "LGBMClassifier", "LGBMRegressor",
                          "LGBMRanker"]),
    ("Callbacks", ["early_stopping", "print_evaluation",
                   "record_evaluation", "reset_parameter"]),
    ("Plotting", ["plot_importance", "plot_metric", "plot_tree",
                  "create_tree_digraph"]),
]


def main() -> None:
    out = io.StringIO()
    out.write(
        "# Python API reference\n\n"
        "Generated from the package docstrings "
        "(`scripts/gen_python_api.py`);\n"
        "the surface mirrors the reference's `docs/Python-API.md` "
        "listing.\n\n")
    for title, names in SECTIONS:
        out.write(f"## {title}\n\n")
        for n in names:
            obj = getattr(lgb, n)
            doc = (inspect.getdoc(obj) or "").strip().split("\n")[0]
            if inspect.isclass(obj):
                sig = str(inspect.signature(obj.__init__)) \
                    .replace("self, ", "").replace("(self)", "()")
                out.write(f"### `{n}{sig}`\n\n{doc}\n\n")
                meths = [m for m, f in sorted(vars(obj).items())
                         if not m.startswith("_")
                         and (callable(f) or isinstance(f, property))]
                if meths:
                    out.write("Methods/properties: "
                              + ", ".join(f"`{m}`" for m in meths) + "\n\n")
            else:
                sig = str(inspect.signature(obj))
                if len(sig) > 70:
                    sig = ("("
                           + ", ".join(inspect.signature(obj).parameters)
                           + ")")
                out.write(f"### `{n}{sig}`\n\n{doc}\n\n")
    dest = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "Python-API.md")
    with open(dest, "w") as f:
        f.write(out.getvalue())
    print(f"wrote {dest}")


if __name__ == "__main__":
    main()
