"""What ``models.lm``'s rule keeps of a configuration's recomputed blocks,
and what the compiler holds beside it: the calibration's record and the tool
for the next shape. No chip: the step is traced here and, with ``--compile``,
compiled for a described v5e (the TPU's compiler is installed).

    python3 scripts/recompute_probe.py gpt2-medium brumby-14b --compile
    python3 scripts/recompute_probe.py gpt2-medium --batch 16 --compile
    python3 scripts/recompute_probe.py brumby-14b --keep none --compile

For each configuration of ``benchmark/configs``: every named kind's bytes
and operations over the layers in the rule's order, the bytes of the
parameters, the rule's estimate of the step with nothing kept and its parts
(what the layers hold for backward, what the widest block stores, a block
of logits), what it kept at ``--device-gib`` of memory and the estimate
with that; with ``--compile`` the compiler's ``memory_analysis()`` of the same step beside it (arguments,
temporaries, their sum, and whether the text holds a ``.remat``
instruction). ``--keep all|none`` overrides the rule for the compilation
(what the estimate is held against), ``--batch`` the configuration's batch.
One JSON line a configuration."""

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import optax

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

GIB = 2**30


def probe(config, device_gib, keep, batch, compile_it):
    from benchmark import configs
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from torchmpi_tpu.models import lm

    cfg = configs.load(config)
    if batch:
        cfg["per_chip_batch"] = batch
    built = configs.build(config, cfg)
    seen = {}
    rule = lm.kinds_kept

    def recorded(limit, parameters, beside, kinds):
        kept = rule(limit, parameters, beside, kinds)
        seen.update(limit=limit, parameters=parameters, beside=beside,
                    kinds=kinds, rule=kept)
        if keep == "all":
            kept = rule(None, parameters, beside, kinds)
        elif keep == "none":
            kept = ()
        seen["kept"] = kept
        return kept

    beside = lm.step_bytes_beside

    def parts(held, stored, logits):
        seen.update(held=held, stored=stored, logits=logits)
        return beside(held, stored, logits)

    lm.kinds_kept, lm.step_bytes_beside = recorded, parts
    lm.device_bytes = lambda: int(device_gib * GIB)
    one_chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def step(params, opt_state, state, tokens):
        if state is None:
            loss, grads = jax.value_and_grad(built.loss_fn)(params, tokens)
        else:
            (loss, state), grads = jax.value_and_grad(
                built.loss_fn, has_aux=True)(params, state, tokens)
        updates, opt_state = built.optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, state, loss

    params, state = jax.eval_shape(built.state_at, jax.random.PRNGKey(0))
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    tokens = jax.ShapeDtypeStruct(
        (cfg["per_chip_batch"], cfg["sequence_length"]), jnp.int32,
        sharding=one_chip)
    began = time.monotonic()
    lowered = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
        place(params), place(jax.eval_shape(built.optimizer.init, params)),
        place(state), (tokens, tokens))
    line = {"config": config, "batch": cfg["per_chip_batch"],
            "lower_s": round(time.monotonic() - began, 1)}
    if seen:
        kinds = seen["kinds"]
        fixed = 4 * seen["parameters"] + seen["beside"]
        line.update(
            kinds={k: {"GiB": round(kinds[k][0] / GIB, 4),
                       "ops_per_byte": round(kinds[k][1] / kinds[k][0], 1)}
                   for k in rule(None, 0, 0, kinds)},
            parameters_GiB=round(seen["parameters"] / GIB, 3),
            beside_GiB=round(seen["beside"] / GIB, 3),
            beside_parts_GiB={k: round(seen[k] / GIB, 3)
                              for k in ("held", "stored", "logits")},
            estimate_nothing_kept_GiB=round(fixed / GIB, 3),
            rule_keeps=list(seen["rule"]), compiled_with=list(seen["kept"]),
            estimate_GiB=round(
                (fixed + sum(kinds[k][0] for k in seen["kept"])) / GIB, 3))
    if compile_it:
        began = time.monotonic()
        jax.config.update("jax_enable_compilation_cache", False)
        compiled = lowered.compile()
        memory = compiled.memory_analysis()
        line.update(
            compile_s=round(time.monotonic() - began, 1),
            arguments_GiB=round(memory.argument_size_in_bytes / GIB, 3),
            temporaries_GiB=round(memory.temp_size_in_bytes / GIB, 3),
            compiled_GiB=round((memory.argument_size_in_bytes
                                + memory.temp_size_in_bytes) / GIB, 3),
            temporaries_bytes=memory.temp_size_in_bytes,
            remat_instructions=compiled.as_text().count(".remat"))
    print(json.dumps(line), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("configs", nargs="+")
    ap.add_argument("--device-gib", type=float, default=15.75)
    ap.add_argument("--keep", choices=("rule", "all", "none"), default="rule")
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--compile", action="store_true")
    args = ap.parse_args()
    for config in args.configs:
        probe(config, args.device_gib, args.keep, args.batch, args.compile)


if __name__ == "__main__":
    main()
