"""Driver of the cells that train a model: `Accelerator.prepare` and the fused
`accelerator.train_step()`, fed by the program's own prepared `DataLoader`.

Set-up builds ONE job (the compiled step with its state and its feed), drives it
from the seed through its first steps — which compile, and which the reference
follows afterwards — and hands that same job to the window. The window is every
optimizer step completed between its first instant and `block_until_ready` on
the last step's outputs; the rate is all their tokens over all that time.
"""

from __future__ import annotations

import math
import time

import numpy as np

from chipbench import harness, traffic_gen

#: Steps the reference follows (the contract's three).
CHECKED_STEPS = 3


class Job:
    """The compiled step with its state and its feed: what set-up builds and the
    window drives."""

    def __init__(self, cell, seed: int, accelerator_overrides: dict | None = None):
        import jax
        import optax
        from accelerate_tpu import Accelerator, SimpleDataLoader
        from accelerate_tpu.data_loader import BatchSampler, SeedableRandomSampler

        spec = cell.spec
        self.cell = cell
        self.reference = harness.load_module("reference", cell.config["family"], cell.root)
        self.params0 = self.reference.init_params(cell.config, harness.seed_key(seed), spec["param_dtype"])
        adapter = harness.load_module("adapters", cell.config["family"], cell.root)
        model = adapter.build_model(cell.config, self.params0, spec["param_dtype"])
        self.batch = int(spec["batch"])
        self.seq_len = int(cell.traffic["seq_len"])
        columns = traffic_gen.training_rows(cell.traffic, cell.config["vocab_size"], seed)
        n = len(next(iter(columns.values())))
        rows = [{k: v[i] for k, v in columns.items()} for i in range(n)]
        sampler = SeedableRandomSampler(num_samples=n, seed=int(seed) % (1 << 31))
        loader = SimpleDataLoader(rows, BatchSampler(sampler, self.batch, drop_last=True))
        self.accelerator = Accelerator(**dict(spec.get("accelerator", {}), **(accelerator_overrides or {})))
        self.learning_rate = float(spec["learning_rate"])
        self.pmodel, self.popt, self.loader = self.accelerator.prepare(
            model, optax.adamw(self.learning_rate), loader)
        self.step_fn = self.accelerator.train_step()
        self.stream = self._cycle()
        jax.block_until_ready(self.pmodel.params)

    def _cycle(self):
        while True:
            yield from self.loader

    def close(self) -> None:
        """End the feed's pass through the loader (it registers itself with the
        program's gradient state while it iterates)."""
        self.stream.close()

    @property
    def tokens_per_step(self) -> int:
        return self.batch * self.seq_len


def find_mu(state):
    """Adam's first moment inside an optax state, wherever it is nested."""
    if hasattr(state, "mu"):
        return state.mu
    children = state if isinstance(state, (tuple, list)) else (getattr(state, "inner_state", None),)
    for child in children:
        found = None if child is None else find_mu(child)
        if found is not None:
            return found
    return None


def first_steps(job: Job) -> dict:
    """The job's first steps, through the window's own feed and call. Keeps what
    the reference will be held against: each step's batch and loss, the first
    gradient as the optimizer got it (Adam's first moment after one step is
    (1 - b1) x that gradient) with its norm a leaf, and the norm a leaf of the
    parameters' change after the steps."""
    import jax
    import jax.numpy as jnp

    leaf_norms = jax.jit(job.reference.leaf_norms)
    batches, losses, grad, grad_norms = [], [], None, None
    for step in range(CHECKED_STEPS):
        batch = next(job.stream)
        batches.append(jax.device_get(batch))
        losses.append(job.step_fn(batch))
        if step == 0:
            mu = find_mu(job.popt.opt_state)
            if mu is None:
                raise RuntimeError("no Adam first moment in the optimizer's state")
            b1 = job.reference.ADAMW["b1"]
            # on the host: a device op a leaf would be a dozen more programs to compile and cache
            grad = jax.tree_util.tree_map(lambda m: np.asarray(m, np.float32) / (1.0 - b1), jax.device_get(mu))
            grad_norms = {k: v / (1.0 - b1) for k, v in leaf_norms(mu).items()}
    change = leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), job.pmodel.params, job.params0))
    return jax.device_get({"batches": batches, "losses": jnp.stack(losses), "first_grad": grad,
                           "first_grad_norms": grad_norms, "change_norms": change})


def measure(job: Job, seconds: float, trace, max_in_flight: int, clock=time.perf_counter) -> dict:
    """The measured window."""
    import jax

    tracing = trace.enabled
    losses, data_wait, dispatch = [], [], []
    t0 = clock()
    while True:
        a = clock()
        if a - t0 >= seconds:
            break
        with harness.span("bench.next_batch", tracing):
            batch = next(job.stream)
        b = clock()
        with harness.span("bench.train_call", tracing):
            loss = job.step_fn(batch)
        c = clock()
        data_wait.append(b - a)
        dispatch.append(c - b)
        losses.append(loss)
        if len(losses) > max_in_flight:
            # a bounded run-ahead, as a loop that logs its loss has
            jax.block_until_ready(losses[-max_in_flight - 1])
        trace.poll(c - t0)
    jax.block_until_ready((losses[-1], job.pmodel.params))
    t1 = clock()
    trace.stop()
    return {"t0": t0, "t1": t1, "losses": losses, "data_wait": data_wait, "dispatch": dispatch}


def worst_leaf_gap(program: dict, reference: dict) -> float:
    """The widest gap between the program's norm of a leaf and the reference's,
    against the reference's norm of that leaf or of the median leaf, whichever
    is larger (some leaves' gradients are all but zero)."""
    floor = float(np.median([float(v) for v in reference.values()]))
    return max(abs(float(program[k]) - float(v)) / max(float(v), floor) for k, v in reference.items())


def cosine_gap(program, reference) -> float:
    """1 - the cosine between two trees taken as one long vector each. Rounding
    in a lower precision turns a gradient where it hardly changes its length,
    so this is the number that tells precisions apart; steady from seed to
    seed, because the large leaves carry it."""
    a = [np.asarray(x, np.float64).ravel() for x in jax_leaves(program)]
    b = [np.asarray(x, np.float64).ravel() for x in jax_leaves(reference)]
    dot = sum(float(x @ y) for x, y in zip(a, b))
    lengths = math.sqrt(sum(float(x @ x) for x in a) * sum(float(y @ y) for y in b))
    return 1.0 - dot / lengths if lengths > 0 else 1.0  # no gradient at all has no direction in common


def jax_leaves(tree) -> list:
    import jax

    return jax.tree_util.tree_leaves(tree)


def compare(first: dict, expected: dict) -> dict:
    """The numbers `correct` is decided on, program against reference."""
    return {
        "first_grad_cosine_gap": cosine_gap(first["first_grad"], expected["first_grad"]),
        "loss_gap": float(np.max(np.abs(np.asarray(first["losses"], np.float64)
                                        - np.asarray(expected["losses"], np.float64)))),
        "first_grad_norm_gap": worst_leaf_gap(first["first_grad_norms"], expected["first_grad_norms"]),
        "change_norm_gap": worst_leaf_gap(first["change_norms"], expected["change_norms"]),
    }


def reference_steps(job: Job, first: dict, precision: str = "float32") -> dict:
    return job.reference.train_steps(job.params0, job.cell.config, first["batches"],
                                     job.learning_rate, precision)


def check(job: Job, first: dict, window_losses: np.ndarray) -> dict:
    limits = job.cell.spec["correct"]
    t = time.perf_counter()
    expected = reference_steps(job, first)
    numbers = compare(first, expected)
    numbers["nonfinite_losses"] = float(np.count_nonzero(~np.isfinite(window_losses)))
    shown = {k: {"value": v, "limit": limits[k + "_limit"]} for k, v in numbers.items()}
    correct = all(math.isfinite(e["value"]) and e["value"] <= e["limit"] for e in shown.values())
    harness.log(check="first steps against the float32 reference", steps=CHECKED_STEPS,
                reference_s=round(time.perf_counter() - t, 3),
                program_losses=[float(x) for x in first["losses"]],
                reference_losses=[float(x) for x in expected["losses"]], **shown, correct=correct)
    return {"correct": correct, "numbers": numbers}


def run(cell, args, device: dict, ledger, t_process_start: float) -> dict:
    import jax
    import jax.numpy as jnp

    job = Job(cell, args.seed)
    first = first_steps(job)
    for _ in range(int(cell.spec.get("warm_steps", 5))):
        loss = job.step_fn(next(job.stream))
    jax.block_until_ready((loss, job.pmodel.params))
    harness.log(phase="warm-up", seconds=round(time.perf_counter() - t_process_start, 3), **ledger.line())
    compiles_before = ledger.compiles
    spec_trace = cell.spec.get("trace", {})
    trace = harness.TraceWindow(bool(args.trace), spec_trace.get("start_after_s", 2.0),
                                spec_trace.get("length_s", 2.0))
    window = measure(job, float(args.seconds), trace, int(cell.spec.get("max_in_flight", 4)))
    compiles_in_window = ledger.compiles - compiles_before
    peak = harness.memory_peak_bytes(cell.chips)
    steps = len(window["losses"])
    wall = window["t1"] - window["t0"]
    values = {"train_tokens_per_s": steps * job.tokens_per_step / wall,
              "setup_s": window["t0"] - t_process_start}
    window_losses = np.asarray(jax.device_get(jnp.stack(window["losses"])), np.float64)
    harness.log(phase="window", seconds=round(wall, 3), steps=steps, compiles_in_window=compiles_in_window,
                **{k: round(v, 4) for k, v in values.items()}, **ledger.line())

    reduced = trace.reduce(cell.chips)
    if args.trace:
        context = {
            "cell": cell, "trace": reduced, "window": window, "seconds": float(args.seconds),
            "peak_bytes": peak, "compiles_in_window": compiles_in_window,
            "peaks": harness.peaks_for(device["kind"], cell.root),
            "batch": job.batch, "seq_len": job.seq_len, "chips": cell.chips,
        }
        values.update(harness.read_per_layer(cell, context))
    outcome = check(job, first, window_losses)
    job.close()
    return harness.result_line(cell, bool(args.trace), device, outcome["correct"], steps, 0,
                               values, reduced, peak)


def control(cell, seed: int, seconds: float, control_spec: dict | None, ledger) -> dict:
    """The check's numbers of the first steps: of the program as the cell runs
    it (`control_spec` None), of the program with a lower-precision path of its
    own switched on (`{"accelerator": {...}}`), or of the reference put in the
    program's place and computed in a lower precision (`{"precision": ...}`).
    Needs no measured window."""
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    spec = control_spec or {}
    for state in (AcceleratorState, GradientState, PartialState):
        state._reset_state()  # one process reads many jobs, and the precision is the state's
    job = Job(cell, seed, spec.get("accelerator"))
    first = first_steps(job)
    job.close()
    expected = reference_steps(job, first)
    if "precision" in spec:
        return compare(reference_steps(job, first, spec["precision"]), expected)
    return compare(first, expected)
