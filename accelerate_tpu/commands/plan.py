"""`accelerate-tpu plan` — the sharding-strategy planner as a command.

Searches the tensor-parallel decode layout for a named in-tree model (the
cost-model planner behind ``sharding_rules="auto"``, `parallel/planner.py`)
and prints the chosen plan: per-leaf PartitionSpecs, the emitted
``(pattern, spec)`` rules table, predicted per-chip HBM bytes and predicted
collective traffic per dispatch — plus the same cost model priced over the
family's hand-written table, so the auto-vs-hand comparison is one command.

Planning is pure shape arithmetic: parameter shapes come from
``jax.eval_shape`` over the module's init where the family allows it (no
weight materialization — planning a 70B layout works on a laptop), and the
mesh is abstract (``--tp 64`` needs no devices). Only ``--refine-top-k``
compiles anything: the top-k candidates' params are placed for real and a
one-token forward is timed per candidate (cost model proposes, hardware
disposes), which requires the tp to fit the visible devices."""

import argparse
import json


def register_subcommand(subparsers):
    parser = subparsers.add_parser(
        "plan", help="Search + print a sharding plan for a named model"
    )
    parser.add_argument(
        "model", nargs="?", default="llama-tiny",
        help="Named in-tree model (accelerate_tpu.models registry)",
    )
    parser.add_argument("--tp", type=int, default=2, help="Tensor-parallel degree to plan for")
    parser.add_argument("--num-slots", type=int, default=8, help="Serving slots (decode batch rows)")
    parser.add_argument("--max-length", type=int, default=None, help="Per-slot cache length (default: model max)")
    parser.add_argument("--page-size", type=int, default=16, help="KV pool page size")
    parser.add_argument("--kv-cache-dtype", default="bf16", choices=["bf16", "int8", "fp8_e4m3"],
                        help="KV pool storage dtype the cost model prices")
    parser.add_argument("--weight-dtype", default="bf16", choices=["bf16", "int8"],
                        help="Weight storage dtype (int8 prices quantized kernels + scales)")
    parser.add_argument("--chip", default=None, help="Chip constants (parallel.planner.CHIPS key); default: by backend")
    parser.add_argument("--beam-width", type=int, default=8, help="Beam width for the strategy search")
    parser.add_argument("--refine-top-k", type=int, default=0,
                        help="Compile + time the top-k candidates and pick the measured best "
                        "(needs the plan's mesh to fit the visible devices). Serving plans "
                        "time a one-token forward; --mesh training plans time a fused "
                        "train step (grads + optimizer update included)")
    parser.add_argument("--seq-len", type=int, default=8, help="Init sequence length for shape derivation")
    parser.add_argument("--json", action="store_true", help="Machine-readable plan JSON")
    parser.add_argument(
        "--mesh", default=None,
        help='Training mesh, e.g. "data=4,model=2": switches to the training '
        "planner — params, grads AND optimizer state (ZeRO weight-update "
        "sharding along the data axis) are enumerated and priced together. "
        'Add a pipeline axis ("data=2,model=2,pipeline=2") for the 3D MPMD '
        "planner: byte-balanced (possibly non-uniform) stages, one 2D plan "
        "per stage submesh, and the 1F1B pipeline-bubble term in the "
        "predicted step time",
    )
    parser.add_argument("--batch", type=int, default=8, help="Global batch size (training planner)")
    parser.add_argument("--opt-bytes-per-param", type=float, default=8.0,
                        help="Optimizer bytes/param the cost model prices (fp32 Adam moments: 8)")
    parser.add_argument(
        "--live", action="store_true",
        help="Build --mesh on the visible devices, place all three trees "
        "(params / grads / optimizer state) per plan, and report predicted-vs-live "
        "per-chip bytes off the LIVE shardings (tree_device_nbytes)",
    )
    parser.set_defaults(func=plan_command)
    return parser


#: Families whose modules init from a bare [1, seq] int32 token batch — these
#: plan from `jax.eval_shape` (no weight materialization). Others fall back to
#: building the real bundle.
_CAUSAL_FAMILIES = ("llama", "gpt_neox", "gptj", "opt", "mixtral")


def _model_shapes(name: str, seq_len: int, materialize: bool):
    """(params-or-shapes tree, config, hand rules table, apply_fn-or-None,
    real-params-or-None) for a registry name."""
    import jax
    import jax.numpy as jnp

    from .. import models as model_zoo
    from ..models import CREATE_BY_FAMILY, get_model_family

    family, config = get_model_family(name)
    if materialize or family not in _CAUSAL_FAMILIES:
        bundle = CREATE_BY_FAMILY[family](config, seq_len=seq_len)
        return bundle.params, config, list(bundle.sharding_rules or []), bundle.apply_fn, bundle.params

    module_cls = {
        "llama": model_zoo.LlamaForCausalLM,
        "gpt_neox": model_zoo.GPTNeoXForCausalLM,
        "gptj": model_zoo.GPTJForCausalLM,
        "opt": model_zoo.OPTForCausalLM,
        "mixtral": model_zoo.MixtralForCausalLM,
    }[family]
    module = module_cls(config)
    sample = jnp.zeros((1, min(seq_len, config.max_position_embeddings)), jnp.int32)
    shapes = jax.eval_shape(module.init, jax.random.key(0), sample)
    import importlib

    family_module = importlib.import_module(f"accelerate_tpu.models.{family}")
    rules = list(getattr(family_module, f"{family.upper()}_SHARDING_RULES", None) or [])
    return shapes, config, rules, module.apply, None


def _parse_mesh(spec: str):
    """Parse ``"data=4,model=2"`` into an ordered ``{axis: size}`` dict. A bare
    axis name (no ``=``) takes the remaining visible-device count (one only)."""
    axes = {}
    fill = None
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" in part:
            name, _, size = part.partition("=")
            axes[name.strip()] = int(size)
        else:
            if fill is not None:
                raise SystemExit(f"--mesh {spec!r}: at most one axis may omit its size")
            axes[part] = -1
            fill = part
    if fill is not None:
        import jax

        explicit = 1
        for name, size in axes.items():
            if size > 0:
                explicit *= size
        n = len(jax.devices())
        if n % explicit != 0:
            raise SystemExit(
                f"--mesh {spec!r}: {n} devices not divisible by explicit sizes ({explicit})"
            )
        axes[fill] = n // explicit
    return axes


def _train_plan_command(args, chip):
    """The ``--mesh`` branch: training planner over params+grads+opt state —
    2D ("data", "model"), or the 3D MPMD pipeline planner when the mesh
    carries a "pipeline" axis — optionally measured (``--refine-top-k``) or
    compared against LIVE placements (``--live``)."""
    from ..parallel.planner import (
        measure_train_step,
        plan_train_sharding,
        refine_plans,
        score_rules,
    )

    mesh_axes = _parse_mesh(args.mesh)
    pipelined = int(mesh_axes.get("pipeline", 1)) > 1
    refine = max(0, int(args.refine_top_k))
    if pipelined and refine:
        raise SystemExit(
            "--refine-top-k times single-mesh training plans; an MPMD pipeline "
            "plan's measured step time comes from "
            "`accelerate-tpu bench --mode train --pipeline-ab`"
        )
    params, config, hand_rules, apply_fn, real_params = _model_shapes(
        args.model, args.seq_len, materialize=args.live or refine >= 1
    )
    layered = layered_split = None
    if pipelined:
        # The pipeline planner balances *per-layer* byte weights, so it needs
        # the LayeredApply split. split() is pure pytree indexing — it works
        # on the eval_shape tree, so deviceless 3D planning stays deviceless.
        from ..models import get_model_family, layered_for_family

        family, _ = get_model_family(args.model)
        layered = layered_for_family(family, config)
        layered_split = layered.split(params)
    plan = plan_train_sharding(
        params,
        mesh_axes,
        batch=args.batch,
        seq=args.seq_len,
        opt_bytes_per_param=args.opt_bytes_per_param,
        weight_dtype=args.weight_dtype,
        chip=chip,
        beam_width=args.beam_width,
        layered_split=layered_split,
        top_k=max(refine, 1),
    )
    measurements = None
    if refine >= 1:
        # Measured selection: place each candidate's three trees on the live
        # mesh and time a fused train step (value_and_grad + optimizer update)
        # — the training twin of the serving path's one-token forward.
        plans = plan if isinstance(plan, list) else [plan]
        live_mesh = _build_live_mesh(mesh_axes)
        plan, measured = refine_plans(
            plans,
            lambda p: measure_train_step(
                apply_fn, real_params, live_mesh, p.rules,
                opt_rules=p.opt_rules, batch=args.batch, seq=args.seq_len,
            ),
        )
        measurements = [seconds for _, seconds in measured]
    # The hand-written family tables are single-mesh: there is nothing to
    # score them against on a pipeline mesh (that gap is the point).
    hand = (
        score_rules(
            params, mesh_axes, hand_rules,
            chip=chip, workload=plan.workload, weight_dtype=args.weight_dtype,
        )
        if hand_rules and not pipelined
        else None
    )
    if args.live:
        live = (
            _live_mpmd_bytes(plan, mesh_axes, real_params, layered)
            if pipelined
            else _live_train_bytes(plan, mesh_axes, real_params)
        )
    else:
        live = None

    if args.json:
        payload = {"model": args.model, "mesh": mesh_axes, "plan": plan.to_json()}
        if hand is not None:
            payload["hand_rules"] = {
                "rules": [[p, list(s)] for p, s in hand.rules],
                "predicted": hand.to_json()["predicted"],
                "modeled_cost": hand.cost.total,
            }
            payload["plan"]["modeled_cost"] = plan.cost.total
            payload["auto_beats_hand"] = plan.cost.total <= hand.cost.total
        if measurements is not None:
            payload["refine_measurements_s"] = measurements
        if live is not None:
            payload["live"] = live
        print(json.dumps(payload, indent=2))
        return payload

    print(f"[plan] {args.model} | mesh={mesh_axes} | batch={args.batch} | "
          f"training (opt {args.opt_bytes_per_param} B/param) weights={args.weight_dtype}")
    print()
    print(plan.describe())
    if measurements is not None:
        print()
        print(f"measure-and-refine (top-{len(measurements)}, fused train step):")
        for i, seconds in enumerate(measurements):
            print(f"  candidate {i}: {seconds * 1e6:.1f} us")
    if hand is not None:
        print()
        verdict = "matches or beats" if plan.cost.total <= hand.cost.total else "LOSES TO"
        print(
            f"hand-written family table: modeled cost {hand.cost.total:.3e} "
            f"(per-chip {int(hand.cost.per_chip_total_bytes)} bytes, "
            f"ici {int(hand.cost.collective_bytes)} B/dispatch) — "
            f"auto plan ({plan.cost.total:.3e}) {verdict} it"
        )
    if live is not None:
        print()
        print("predicted vs live per-chip bytes (tree_device_nbytes, device 0):")
        for tree in ("params", "grads", "opt_state"):
            row = live[tree]
            print(
                f"  {tree:<10} predicted {row['predicted_bytes']:>12}  "
                f"live {row['live_bytes']:>12}  error {row['error_pct']:.2f}%"
            )
    return plan


def _build_live_mesh(mesh_axes):
    """A real `Mesh` shaped like the ``--mesh`` axes dict on the visible
    devices (SystemExit when the host is too small for the product)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    sizes = [int(s) for s in mesh_axes.values()]
    n_devices = int(np.prod(sizes))
    devices = jax.devices()
    if len(devices) < n_devices:
        raise SystemExit(
            f"this step needs {n_devices} devices for mesh {dict(mesh_axes)}, "
            f"have {len(devices)}"
        )
    return Mesh(np.array(devices[:n_devices]).reshape(sizes), tuple(mesh_axes))


def _bytes_row(predicted, live):
    predicted, live = float(predicted), float(live)
    err = abs(predicted - live) / live * 100.0 if live else 0.0
    return {
        "predicted_bytes": int(predicted),
        "live_bytes": int(live),
        "error_pct": err,
    }


def _live_train_bytes(plan, mesh_axes, real_params):
    """Place params, a zeros grads tree, and a freshly-initialized Adam state on
    the real devices per the plan (the same derivation seams `prepare()` uses)
    and measure per-chip bytes off the LIVE shardings."""
    import jax
    import optax

    from ..parallel.sharding import (
        derive_opt_state_shardings,
        derive_tp_param_shardings,
        place_params,
        tree_device_nbytes,
    )

    mesh = _build_live_mesh(mesh_axes)
    dev0 = mesh.devices.flat[0]

    param_shardings = derive_tp_param_shardings(real_params, mesh, plan.rules)
    placed = place_params(real_params, param_shardings)
    grads = place_params(jax.tree_util.tree_map(lambda x: jax.numpy.zeros_like(x), real_params), param_shardings)
    tx = optax.adam(1e-3)
    state_shapes = jax.eval_shape(tx.init, placed)
    opt_shardings = derive_opt_state_shardings(
        state_shapes, mesh, None, plan.rules, opt_rules=plan.opt_rules
    )
    opt_state = jax.jit(tx.init, out_shardings=opt_shardings)(placed)

    return {
        "params": _bytes_row(plan.cost.per_chip_param_bytes, tree_device_nbytes(placed, dev0)),
        # Grads carry the parameter dtype and placement, so the param account
        # predicts them too.
        "grads": _bytes_row(plan.cost.per_chip_param_bytes, tree_device_nbytes(grads, dev0)),
        "opt_state": _bytes_row(plan.cost.per_chip_opt_bytes, tree_device_nbytes(opt_state, dev0)),
    }


def _init_placed_opt_state(tx, placed, opt_shardings):
    """Initialize one stage's Adam state pinned to its derived shardings —
    a helper so each stage's jit is a distinct function object compiled once,
    not a fresh cache built inside the stage loop."""
    import jax

    return jax.jit(tx.init, out_shardings=opt_shardings)(placed)


def _live_mpmd_bytes(plan, mesh_axes, real_params, layered):
    """The ``--live`` account for an MPMD pipeline plan: place every stage's
    params + grads accumulator + Adam state on its OWN pipeline submesh per the
    stage's rules tables (the same derivations `parallel.mpmd` runs) and
    compare the busiest stage's per-chip bytes against the plan's prediction —
    the plan prices exactly the busiest stage, because that chip's HBM is the
    binding constraint."""
    import jax
    import optax

    from ..parallel.mesh import slice_mesh
    from ..parallel.planner import build_stage_tree
    from ..parallel.sharding import (
        derive_opt_state_shardings,
        derive_tp_param_shardings,
        place_params,
        tree_device_nbytes,
    )

    mesh = _build_live_mesh(mesh_axes)
    submeshes = slice_mesh(mesh, "pipeline")
    prelude, layers, tail = layered.split(real_params)
    tx = optax.adam(1e-3)

    param_live, grad_live, opt_live = [], [], []
    for k, submesh in enumerate(submeshes):
        tree = build_stage_tree(prelude, layers, tail, plan.stage_plan, k)
        shardings = derive_tp_param_shardings(tree, submesh, list(plan.stage_rules(k)))
        placed = place_params(tree, shardings)
        grads = place_params(
            jax.tree_util.tree_map(lambda x: jax.numpy.zeros_like(x), tree), shardings
        )
        state_shapes = jax.eval_shape(tx.init, placed)
        opt_shardings = derive_opt_state_shardings(
            state_shapes, submesh, None, list(plan.stage_rules(k)),
            opt_rules=list(plan.stage_opt_rules(k) or []) or None,
        )
        opt_state = _init_placed_opt_state(tx, placed, opt_shardings)
        dev = submesh.devices.flat[0]
        param_live.append(tree_device_nbytes(placed, dev))
        grad_live.append(tree_device_nbytes(grads, dev))
        opt_live.append(tree_device_nbytes(opt_state, dev))

    out = {
        "params": _bytes_row(plan.cost.per_chip_param_bytes, max(param_live)),
        "grads": _bytes_row(plan.cost.per_chip_param_bytes, max(grad_live)),
        "opt_state": _bytes_row(plan.cost.per_chip_opt_bytes, max(opt_live)),
    }
    out["per_stage_param_bytes"] = [int(b) for b in param_live]
    return out


def plan_command(args):
    import numpy as np

    from ..parallel.planner import (
        CHIPS,
        measure_forward_step,
        plan_serving_sharding,
        refine_plans,
        score_rules,
    )

    chip = CHIPS[args.chip] if args.chip else None
    if args.mesh:
        return _train_plan_command(args, chip)
    refine = max(0, int(args.refine_top_k))
    params, config, hand_rules, apply_fn, real_params = _model_shapes(
        args.model, args.seq_len, materialize=refine >= 1
    )
    max_length = int(args.max_length or config.max_position_embeddings)
    num_pages = args.num_slots * -(-max_length // args.page_size) + 1

    mesh = {"model": int(args.tp)}
    plan_kwargs = dict(
        num_slots=args.num_slots,
        page_size=args.page_size,
        num_pages=num_pages,
        kv_cache_dtype=args.kv_cache_dtype,
        weight_dtype=args.weight_dtype,
        chip=chip,
        beam_width=args.beam_width,
    )
    measurements = None
    if refine >= 1:
        # Measured selection needs real devices: build the live submesh and
        # time a one-token forward per candidate (refine-top-k 1 still
        # measures the single chosen plan).
        from ..parallel.sharding import serving_tp_mesh

        live_mesh = serving_tp_mesh(args.tp)
        plans = plan_serving_sharding(params, live_mesh, config, top_k=refine, **plan_kwargs)
        if not isinstance(plans, list):
            plans = [plans]
        plan, measured = refine_plans(
            plans,
            lambda p: measure_forward_step(
                apply_fn, real_params, live_mesh, p.rules, batch=1
            ),
        )
        measurements = [(i, seconds) for i, (_, seconds) in enumerate(measured)]
    else:
        plan = plan_serving_sharding(params, mesh, config, **plan_kwargs)

    hand = (
        score_rules(
            params, mesh, hand_rules,
            chip=chip, workload=plan.workload, weight_dtype=args.weight_dtype,
        )
        if hand_rules
        else None
    )

    if args.json:
        payload = {"model": args.model, "plan": plan.to_json()}
        if hand is not None:
            payload["hand_rules"] = {
                "rules": [[p, list(s)] for p, s in hand.rules],
                "predicted": hand.to_json()["predicted"],
                "modeled_cost": hand.cost.total,
            }
            payload["plan"]["modeled_cost"] = plan.cost.total
            payload["auto_beats_hand"] = plan.cost.total <= hand.cost.total
        if measurements is not None:
            payload["refine_measurements_s"] = [s for _, s in measurements]
        print(json.dumps(payload, indent=2))
        return payload

    print(f"[plan] {args.model} | tp={args.tp} | slots={args.num_slots} | "
          f"kv={args.kv_cache_dtype} "
          f"weights={args.weight_dtype}")
    print()
    print(plan.describe())
    if measurements is not None:
        print()
        print("measure-and-refine (top-{}):".format(len(measurements)))
        for i, seconds in measurements:
            print(f"  candidate {i}: {seconds * 1e6:.1f} us")
    if hand is not None:
        print()
        verdict = "matches or beats" if plan.cost.total <= hand.cost.total else "LOSES TO"
        print(
            f"hand-written family table: modeled cost {hand.cost.total:.3e} "
            f"(per-chip {int(hand.cost.per_chip_total_bytes)} bytes, "
            f"ici {int(hand.cost.collective_bytes)} B/dispatch) — "
            f"auto plan ({plan.cost.total:.3e}) {verdict} it"
        )
    return plan
