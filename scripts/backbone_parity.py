#!/usr/bin/env python3
"""
A backbone configuration at its published widths against its plain
reference, on the device JAX finds: one batch of windows through the
program's forward, and one optimizer step's loss and gradient norms,
with the reference computed in blocks of windows so that it fits.

    python3 scripts/backbone_parity.py [--config <configs/*.json>] [--seed N] [--block 4]

(``--config benchmarks/chip/configs/keye-vl2-30b-a3b-50tag-lb8192.json
--block 1`` for the sparse-attention backbone, ``--config
benchmarks/chip/configs/laguna-xs2-50tag-lb8192.json --block 1`` for the
banded one: a block is whole windows.)

Prints one JSON object (and writes it to ``chiprun_out/backbone_parity.json``):
the worst fraction of scale of the forward, the loss of both sides, and
the gradient's global and per-leaf norms' relative differences. A
builder's tool, not a test: the tier-1 tests hold the same comparisons
at toy widths on the CPU.
"""

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
DEFAULT_CONFIG = os.path.join(ROOT, "benchmarks", "chip", "configs", "lfm2-8b-a1b-50tag-lb512.json")


def load_reference(name: str):
    path = os.path.join(ROOT, "benchmarks", "chip", "reference", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reference_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default=DEFAULT_CONFIG)
    parser.add_argument("--seed", type=int, default=2147483659)
    parser.add_argument("--block", type=int, default=4)
    parser.add_argument("--overrides", default="{}", help="JSON of estimator keys (toy widths)")
    parser.add_argument("--forward-seeds", type=int, default=0,
                        help="further seeds of weights and windows through the forward alone")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from gordo_tpu import serializer
    from gordo_tpu.models.backbone import forward_backbone_aux, trained_param_count
    from gordo_tpu.models.training import split_fit_kwargs
    from gordo_tpu.ops.losses import resolve_loss, weighted_mean_loss

    with open(args.config) as f:
        config = json.load(f)
    (path, definition), = config["estimator"].items()
    definition = {**definition, **json.loads(args.overrides)}
    estimator = serializer.from_definition({path: definition})
    fit_kwargs, factory_kwargs = split_fit_kwargs(
        {**estimator.kwargs, "n_features": config["tags"], "n_features_out": config["tags"]}
    )
    spec = estimator._build_spec(factory_kwargs)
    batch, lookback, tags = int(fit_kwargs["batch_size"]), spec.lookback_window, config["tags"]
    reference = load_reference(config["reference"])

    device = jax.devices()[0]
    result = {"device": {"platform": device.platform, "kind": device.device_kind},
              "seed": args.seed, "windows": batch, "lookback": lookback}
    rng = np.random.RandomState(args.seed % (2**32))
    x = rng.uniform(0.0, 1.0, (batch, lookback, tags)).astype(np.float32)
    y = rng.uniform(0.0, 1.0, (batch, tags)).astype(np.float32)
    w = np.ones(batch, np.float32)
    params = jax.jit(lambda key: spec.init_fn()(key, spec))(jax.random.PRNGKey(args.seed % (2**31)))
    result["weights"] = trained_param_count(params)
    result["weights_stated"] = config.get("weights_per_member")

    # the program: forward, then one step's loss and gradient
    started = time.time()
    out, _, aux = jax.jit(lambda p, x: forward_backbone_aux(spec, p, x))(params, x)
    out = np.asarray(out)
    per_sample = resolve_loss(spec.loss)

    def loss_of(p):
        o, penalty, _ = forward_backbone_aux(spec, p, x)
        return weighted_mean_loss(per_sample(o, y), w) + penalty

    def norms_of(p):
        loss, grads = jax.value_and_grad(loss_of)(p)
        return loss, jax.tree_util.tree_map(lambda g: jnp.sqrt(jnp.sum(g * g)), grads)

    loss, leaf_norms = jax.jit(norms_of)(params)
    loss, leaf_norms = float(loss), jax.tree_util.tree_map(float, jax.device_get(leaf_norms))
    result["program_seconds"] = round(time.time() - started, 3)
    result["router_tokens"] = np.asarray(aux["router_tokens"]).tolist() if aux else None
    result["pairs_here"] = np.asarray(aux["pairs_here"]).tolist() if aux else None
    # a sparse-attention backbone's, a banded one's
    for name in ("keys_selected", "keys_causal", "indexer_kl", "pairs_attended", "pairs_multiplied"):
        if aux and name in aux:
            result[name] = np.asarray(aux[name]).tolist()

    # the reference, in blocks of windows
    class Artifact:
        spec_, params_ = spec, params

    layers = reference.layers_of(Artifact)
    started = time.time()
    expected = reference.forward(layers, x, block_windows=args.block)
    scale = max(1.0, float(np.max(np.abs(expected))))
    result["forward_worst_fraction_of_scale"] = float(np.max(np.abs(out - expected))) / scale
    result["forward_scale"] = scale
    if aux:
        blocks = [x[i : i + args.block] for i in range(0, batch, args.block)]
        if hasattr(reference, "router_counts"):
            counts = np.sum([reference.router_counts(layers, b) for b in blocks], axis=0)
        else:  # a reference that counts its selection, or its mask, beside its routing
            found = [reference.counters(layers, b) for b in blocks]
            counts = np.sum([f["routed"] for f in found], axis=0)
            if "kept" in found[0]:
                result["reference_keys_selected"] = np.sum([f["kept"] for f in found], axis=0).tolist()
                result["reference_indexer_kl"] = float(np.mean(np.concatenate([f["kl"] for f in found])))
            else:
                result["reference_pairs_attended"] = np.sum([f["attended"] for f in found], axis=0).tolist()
        moved = np.abs(counts - np.asarray(aux["router_tokens"])).sum(axis=1) / 2
        result["router_pairs_moved_share"] = (moved / counts.sum(axis=1)).tolist()

    # how often rounding moves a window past the benchmark's limit: the
    # forward alone, on further seeds of weights and windows
    fractions = (np.max(np.abs(out - expected), axis=1) / scale).tolist()
    for extra in range(1, args.forward_seeds + 1):
        rng_e = np.random.RandomState((args.seed + extra) % (2**32))
        x_e = rng_e.uniform(0.0, 1.0, (batch, lookback, tags)).astype(np.float32)
        params_e = jax.jit(lambda key: spec.init_fn()(key, spec))(
            jax.random.PRNGKey((args.seed + extra) % (2**31))
        )
        out_e = np.asarray(jax.jit(lambda p, x: forward_backbone_aux(spec, p, x)[0])(params_e, x_e))

        class Seeded:
            spec_, params_ = spec, params_e

        want_e = reference.forward(reference.layers_of(Seeded), x_e, block_windows=args.block)
        scale_e = max(1.0, float(np.max(np.abs(want_e))))
        fractions += (np.max(np.abs(out_e - want_e), axis=1) / scale_e).tolist()
        del params_e
    result["window_fractions_of_scale"] = {
        "windows": len(fractions), "median": float(np.median(fractions)),
        "p90": float(np.percentile(fractions, 90)), "max": float(np.max(fractions)),
        "over_5e-2": int(np.sum(np.asarray(fractions) > 5e-2)),
        "over_2e-2": int(np.sum(np.asarray(fractions) > 2e-2)),
    }

    # one step at seeded weights, as the cell's step check reads it at
    # trained ones (reference.loss_band): the whole batch, in blocks
    ref_loss, ref_norms, _ = reference.blockwise_step(layers, x, y, block=args.block)
    result["reference_seconds"] = round(time.time() - started, 3)
    readings = reference.step_readings(loss, leaf_norms, ref_loss, ref_norms)
    result.update(
        loss=loss, reference_loss=ref_loss, loss_relative_difference=readings["loss"],
        grad_norm=readings["program"]["grad_norm"],
        reference_grad_norm=readings["reference"]["grad_norm"],
        grad_norm_relative_difference=readings["grad_norm"],
        worst_leaf_norm_relative_difference=readings["leaf"], worst_leaf=readings["worst_leaf"],
    )
    if "indexer_leaf" in readings:
        result.update(
            worst_indexer_leaf_norm_relative_difference=readings["indexer_leaf"],
            worst_indexer_leaf=readings["worst_indexer_leaf"],
        )
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "backbone_parity.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
