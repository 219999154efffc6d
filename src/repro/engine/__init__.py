"""repro.engine — the plan-based multi-mode inference engine (paper §4).

The framework-wide execution contract: every dense compute in the repo —
CNN convolutions, depthwise 1-D convs inside SSM blocks, attention
projections, FFN / MoE expert GEMMs, embeddings / LM heads — routes through
`engine.conv2d / conv1d_depthwise / dense / einsum`, i.e. through the
*same* engine operating in different modes, exactly as the MMIE chip runs
both conv and FC layers on the same 192 PEs.

Two-phase compile/execute model (the paper's network-level scheduling):

  * `EngineConfig` (config.py)  — frozen, hashable execution config
    (backend, interpret, accum, policy); ambient via `using_config`,
    jit-static friendly. `using_backend` is a thin shim over it.
  * `Program` / `NetworkPlan` / `compile` (program.py) — ordered op graphs
    from layer tables (`models.cnn.program`) or traced forwards
    (`trace_program`), planned whole-network into Table-4 aggregates and a
    jitted `CompiledNet.apply` with per-layer backend selection
    (`policy="auto"`).

Per-op pieces (all pure, jit-friendly, singleton-free):

  * `EnginePlan` / `OpSpec` (plan.py) — hashable per-op plan/op from shapes
    alone: Table-3 mode, MXU tiling, analytic cost (Eqs. 15-18);
  * backend registry (dispatch.py) — "pallas" / "xla" / "ref", extensible
    via `register_backend`;
  * `Ledger` + `tracking()` (ledger.py) — explicit, scoped analytics;
  * kernel autotuner (tune.py) — per-op Pallas tile configs, benchmarked
    once and persisted to `.tuning/<device_kind>.json`, selected by
    `EngineConfig.tuning` and pinned at `engine.compile` time. Every
    dense/conv op also takes `bias=` / `act=` — a fused epilogue applied
    in the kernel's fp32 accumulator on the Pallas backend.
"""
from repro.engine import tune  # noqa: F401
from repro.engine.api import (  # noqa: F401
    capturing, conv1d_depthwise, conv2d, dense, einsum, matmul, paged_gather,
    proj, replaying)
from repro.engine.config import (  # noqa: F401
    EngineConfig, current_config, default_backend, in_config_context,
    set_default_config, using_backend, using_config)
from repro.engine.dispatch import (  # noqa: F401
    EPILOGUE_ACTS, EngineBackend, apply_epilogue, backend_names, get_backend,
    register_backend)
from repro.engine.ledger import (  # noqa: F401
    Ledger, OpRecord, is_tracking, record, tracking)
from repro.engine.parallel import (  # noqa: F401
    ParallelConfig, data_groups, make_mesh)
from repro.engine.plan import (  # noqa: F401
    PRECISIONS, EnginePlan, OpSpec, ShardDecision, auto_backend, dense_spec,
    parse_einsum, plan_conv1d_depthwise, plan_conv2d, plan_einsum,
    plan_gather, plan_op, supports_int8, with_precision)
from repro.engine.program import (  # noqa: F401
    CompiledNet, NetworkPlan, Program, compile, infer_batch_axes,
    plan_network, trace_program)
