"""The paper's own scenario: run AlexNet / VGG-16 / ResNet-50 inference
through the multi-mode engine and print the MMIE-projected per-layer
analytics (Fig. 5) alongside the functional forward pass.

Uses the plan-based `repro.engine` API: the forward pass is wrapped in
`engine.tracking()`, which yields the analytic `Ledger` (identical totals
to the legacy `MultiModeEngine` ledger). `--compiled` switches to the
two-phase path instead: `engine.compile(cnn.program(net), EngineConfig)`
plans the whole network up front (Table-4 aggregates with no forward pass)
and runs the jitted `CompiledNet.apply`.

  PYTHONPATH=src python examples/cnn_inference.py [--net resnet50]
  PYTHONPATH=src python examples/cnn_inference.py --compiled --policy auto
"""
import argparse

import jax
import jax.numpy as jnp

from repro import engine
from repro.core.quant import ACT_FORMAT, WEIGHT_FORMAT, quantize
from repro.launch import compile_cache
from repro.models import cnn


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", default="alexnet",
                    choices=["alexnet", "vgg16", "resnet50"])
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--backend", default="xla",
                    choices=["xla", "ref", "pallas"])
    ap.add_argument("--fixed-point", action="store_true",
                    help="simulate the paper's 16-bit quantization")
    ap.add_argument("--compiled", action="store_true",
                    help="whole-network compile/execute path")
    ap.add_argument("--policy", default="fixed", choices=["fixed", "auto"],
                    help="backend-selection policy for --compiled")
    args = ap.parse_args(argv)
    compile_cache.enable()

    net = args.net
    h, w, c = cnn.CNNS[net].input_hw_c
    key = jax.random.PRNGKey(0)
    params = cnn.init_cnn(net, key)
    x = jax.random.normal(key, (args.batch, h, w, c), jnp.float32)

    if args.fixed_point:
        params = jax.tree_util.tree_map(
            lambda t: quantize(t, WEIGHT_FORMAT), params)
        x = quantize(x, ACT_FORMAT)

    if args.compiled:
        cfg = engine.EngineConfig(backend=args.backend, policy=args.policy)
        compiled = engine.compile(cnn.program(net, batch=args.batch), cfg)
        row = compiled.cost
        print(f"{net}: NetworkPlan over {len(compiled.plan.plans)} ops "
              f"(no forward pass needed)")
        print(f"  conv {row['conv_ms']:.1f} ms @200MHz · "
              f"fc {row['fc_ms']:.2f} ms @40MHz · "
              f"MA {row['conv_MA_MB'] + row['fc_MA_MB']:.1f} MB · "
              f"conv eff {row['conv_eff']:.3f}")
        print(f"  per-layer backends: {compiled.backends()}")
        logits = compiled.apply(params, x)
        print(f"  logits {logits.shape}, top-1 idx "
              f"{int(jnp.argmax(logits[0]))}")
        return

    with engine.tracking() as ledger:
        logits = cnn.apply_cnn(net, params, x, backend=args.backend)
    print(f"{net}: logits {logits.shape}, top-1 idx "
          f"{int(jnp.argmax(logits[0]))}")
    print(f"MMIE-projected totals for batch={args.batch}:")
    print(f"  cycles             {ledger.total_cycles:,}")
    print(f"  MACs               {ledger.total_macs:,}")
    print(f"  perf efficiency    {ledger.performance_efficiency:.3f}")
    conv_cyc = sum(r.cost_cycles for r in ledger
                   if r.kind != 'matmul')
    fc_cyc = ledger.total_cycles - conv_cyc
    print(f"  conv latency       {conv_cyc/200e6*1e3:.1f} ms @200MHz")
    print(f"  fc   latency       {fc_cyc/40e6*1e3:.2f} ms @40MHz")
    print("per-op ledger (first 12 rows):")
    for line in ledger.report().splitlines()[:13]:
        print("  " + line)


if __name__ == "__main__":
    main()
