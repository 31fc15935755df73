"""ResNet-50 MFU diagnostic: A/B the normalization variants on hardware.

The measured facts so far (tools/bench_history.jsonl): 29.5% MFU at
batch 64, ~30% at batch 256 (batch size is not the bottleneck), and the
s2d stem lever measured slower (stem contraction width is not the
bottleneck either). ViT trains at 50% MFU on the same chip, so the gap
is convnet-specific. The remaining named suspect is batch-norm: its
per-channel batch reductions sit between every conv and its consumer,
and on TPU a bad interaction there shows up as unfused HBM round-trips
of full activation tensors.

This probe bounds that hypothesis empirically: it times the SAME
training step (bench.py's single-dispatch ``measure`` protocol, see
bench.py ``measure``) across ``models/resnet.py::ResNet.norm_variant`` =

  bn      the production default (bf16 normalize, f32 stats)
  bn_f32  whole norm in f32 (isolates bf16<->f32 casts around stats)
  gn      GroupNorm-32: no batch reduction, fuses as elementwise
  none    identity (diagnostic floor: total cost of normalization)

``bn`` minus ``none`` is the whole normalization budget; if ``gn`` ~=
``none`` but ``bn`` is far above both, the batch-stat reduction (not
the elementwise normalize) is the cost and the fix is a restructured
BN, not a different epsilon. Run on the real chip:

    python tools/mfu_probe.py            # batch 64, 30 steps/variant
    python tools/mfu_probe.py --batch 256 --steps 50

Prints one JSON line per variant (step_time_ms, examples/sec, MFU from
each variant's own compiled-step cost analysis) and a summary line.
Nothing here changes training defaults.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

VARIANTS = ("bn", "bn_f32", "gn", "none", "fused", "fused3", "nf")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--hw", type=int, default=224)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--variants", nargs="*", default=list(VARIANTS))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import _mfu, measure, step_flops
    from pyspark_tf_gke_tpu.models import ResNet50
    from pyspark_tf_gke_tpu.parallel.mesh import batch_sharding, make_mesh
    from pyspark_tf_gke_tpu.train.trainer import TASKS, Trainer
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    dev = jax.devices()[0]
    print(f"device: {dev.device_kind}", file=sys.stderr)
    mesh = make_mesh()
    rng = np.random.default_rng(0)
    hbatch = {
        "image": rng.uniform(0, 1, (args.batch, args.hw, args.hw, 3))
        .astype(np.float32),
        "label": rng.integers(0, 1000, (args.batch,)).astype(np.int32),
    }
    sharding = batch_sharding(mesh)
    results = {}
    for variant in args.variants:
        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                         norm_variant=variant)
        trainer = Trainer(model, TASKS["resnet"](), mesh,
                          learning_rate=1e-3)
        state = trainer.init_state(make_rng(1337),
                                   {k: v[:1] for k, v in hbatch.items()})
        gbatch = {k: jax.device_put(v, sharding) for k, v in hbatch.items()}
        flops = step_flops(trainer, state, gbatch)
        state, _, dt = measure(trainer, state, gbatch, args.steps)
        step_ms = dt / args.steps * 1e3
        mfu = _mfu(flops, step_ms / 1e3, dev.device_kind)
        out = {"variant": variant, "step_time_ms": round(step_ms, 3),
               "examples_per_sec": round(args.batch / (step_ms / 1e3), 1),
               "mfu": round(mfu, 4) if mfu is not None else None,
               "flops_per_step": flops}
        results[variant] = out
        print(json.dumps(out), flush=True)
    if "bn" in results and "none" in results:
        bn, none = results["bn"], results["none"]
        norm_ms = bn["step_time_ms"] - none["step_time_ms"]
        print(json.dumps({
            "summary": "norm budget",
            "norm_cost_ms": round(norm_ms, 3),
            "norm_cost_frac_of_step": round(
                norm_ms / bn["step_time_ms"], 4),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
