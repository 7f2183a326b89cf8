"""Fabric-surrogate CLI (docs/SWEEP.md "Surrogate").

    python -m shadow_tpu.tools.surrogate train DATASET.swds \
        --out MODEL.npz [--holdout fan_in:16] [--steps 300] [--seed 1]
    python -m shadow_tpu.tools.surrogate eval MODEL.npz DATASET.swds \
        [--holdout fan_in:16]

`train` fits the RouteNet-shaped GNN on every point NOT matched by
the holdout predicate (`feature:min` — points with feature >= min
are held out) and, when a holdout is given, prints the surrogate-vs-
simulator per-quantile error table on the held-out fabrics.  `eval`
reloads a saved model and re-renders the table — honest numbers
either way, large errors included.
"""

from __future__ import annotations

import argparse
import json
import sys


def _parse_holdout(text: str | None):
    if text is None:
        return None
    try:
        feature, min_s = text.split(":")
        return feature, float(min_s)
    except ValueError:
        raise SystemExit(f"surrogate: --holdout must be "
                         f"feature:min, got {text!r}")


def print_error_table(tab: dict, out=None) -> None:
    out = out or sys.stdout
    print("surrogate-vs-simulator (held-out fabrics):", file=out)
    print(f"  {'point':<28} {'flows':>6} "
          f"{'p50 err':>8} {'p99 err':>8} {'p999 err':>9} "
          f"{'peak err':>9}", file=out)
    for r in tab["points"]:
        print(f"  {r['point_id'][:28]:<28} {r['flows']:>6} "
              f"{r['rel_err_p50']:>8.1%} {r['rel_err_p99']:>8.1%} "
              f"{r['rel_err_p999']:>9.1%} "
              f"{r.get('rel_err_peak', float('nan')):>9.1%}",
              file=out)
    print(f"  mean: p50 {tab['mean_rel_err_p50']:.1%}, "
          f"p99 {tab['mean_rel_err_p99']:.1%}, "
          f"p999 {tab['mean_rel_err_p999']:.1%}", file=out)


def cmd_train(args) -> int:
    from shadow_tpu.sweep import dataset
    from shadow_tpu.surrogate import features, model, train
    ds = dataset.load(args.dataset)
    samples = features.build_samples(ds)
    holdout = _parse_holdout(args.holdout)
    if holdout:
        tr, held = train.split_samples(samples, *holdout)
    else:
        tr, held = samples, []
    if not tr:
        print("surrogate: holdout leaves no training points",
              file=sys.stderr)
        return 1
    params, hist = train.train(
        tr, seed=args.seed, steps=args.steps,
        log=lambda m: print(m, file=sys.stderr))
    meta = {
        "dataset": ds.meta["name"],
        "seed": args.seed,
        "steps": args.steps,
        "loss_first": round(hist[0], 6),
        "loss_last": round(hist[-1], 6),
        "holdout": args.holdout,
        "trained_points": [s["point_id"] for s in tr],
    }
    print(f"trained on {len(tr)} point(s); loss "
          f"{hist[0]:.4f} -> {hist[-1]:.4f}")
    if held:
        tab = train.error_table(params, held)
        meta["error_table"] = tab
        print_error_table(tab)
    if args.out:
        model.save(args.out, params, meta)
        print(f"model: {args.out}")
    return 0


def cmd_eval(args) -> int:
    from shadow_tpu.sweep import dataset
    from shadow_tpu.surrogate import features, model, train
    params, meta = model.load(args.model)
    ds = dataset.load(args.dataset)
    samples = features.build_samples(ds)
    holdout = _parse_holdout(args.holdout or meta.get("holdout"))
    if holdout:
        trained = set(meta.get("trained_points", []))
        _tr, held = train.split_samples(samples, *holdout)
        leak = [s["point_id"] for s in held
                if s["point_id"] in trained]
        if leak:
            print(f"surrogate: refusing to evaluate — held-out "
                  f"point(s) were in the training set: {leak[:4]}",
                  file=sys.stderr)
            return 1
    else:
        held = samples
    if not held:
        print("surrogate: nothing to evaluate", file=sys.stderr)
        return 1
    tab = train.error_table(params, held)
    print_error_table(tab)
    print(json.dumps({k: v for k, v in tab.items()
                      if k != "points"}, sort_keys=True))
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    ap = argparse.ArgumentParser(prog="shadow_tpu.tools.surrogate",
                                 description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    tr = sub.add_parser("train")
    tr.add_argument("dataset")
    tr.add_argument("--out")
    tr.add_argument("--holdout")
    tr.add_argument("--steps", type=int, default=300)
    tr.add_argument("--seed", type=int, default=1)
    ev = sub.add_parser("eval")
    ev.add_argument("model")
    ev.add_argument("dataset")
    ev.add_argument("--holdout")
    args = ap.parse_args(argv)
    from shadow_tpu.sweep.dataset import DatasetError
    try:
        if args.cmd == "train":
            return cmd_train(args)
        return cmd_eval(args)
    except (DatasetError, ValueError) as e:
        print(f"surrogate: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
