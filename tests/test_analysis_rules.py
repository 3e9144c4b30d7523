"""Linter rule coverage: every rule's flag fixture is caught (and ONLY that
rule), every clean fixture lints silent, suppression comments work, and the
`accelerate-tpu analyze` CLI round-trips --json output and exit codes."""

import json
from pathlib import Path

import pytest

from accelerate_tpu.analysis import (
    RULES,
    RULES_BY_ID,
    analyze_paths,
    analyze_source,
    resolve_rule,
)

pytestmark = pytest.mark.analysis

SAMPLES = Path(__file__).resolve().parent / "test_samples" / "analysis"
RULE_IDS = sorted(RULES_BY_ID)


def test_registry_shape():
    assert len(RULES) >= 8  # the acceptance floor; currently 11
    assert len({r.id for r in RULES}) == len(RULES)
    assert len({r.slug for r in RULES}) == len(RULES)
    for rule in RULES:
        assert rule.fixit and rule.summary
        assert resolve_rule(rule.id) is rule
        assert resolve_rule(rule.slug) is rule
        assert resolve_rule(rule.id.lower()) is rule


@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_flag_fixture_is_caught(rule_id):
    path = SAMPLES / f"{rule_id.lower()}_flag.py"
    findings = analyze_source(path.read_text(), str(path))
    assert findings, f"{path.name} seeded a {rule_id} hazard the linter missed"
    assert {f.rule_id for f in findings} == {rule_id}, (
        f"{path.name} should trip ONLY {rule_id}: {[(f.rule_id, f.line) for f in findings]}"
    )


@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_clean_fixture_is_silent(rule_id):
    path = SAMPLES / f"{rule_id.lower()}_clean.py"
    findings = analyze_source(path.read_text(), str(path))
    assert not findings, (
        f"{path.name} is the sanctioned spelling and must lint clean: "
        f"{[(f.rule_id, f.line) for f in findings]}"
    )


def test_suppression_comments():
    path = SAMPLES / "suppressed.py"
    findings = analyze_source(path.read_text(), str(path))
    assert not findings, [(f.rule_id, f.line) for f in findings]


def test_suppression_variants():
    flagged = "import jax\n\n@jax.jit\ndef f(x):\n    return x.item()\n"
    assert analyze_source(flagged)  # sanity: hazard present
    by_id = flagged.replace("x.item()", "x.item()  # tpu-lint: disable=TPU101")
    by_slug = flagged.replace("x.item()", "x.item()  # tpu-lint: disable=host-sync-item")
    by_all = flagged.replace("x.item()", "x.item()  # tpu-lint: disable=all")
    file_wide = "# tpu-lint: disable-file=TPU101\n" + flagged
    unknown = flagged.replace("x.item()", "x.item()  # tpu-lint: disable=NOPE123")
    assert not analyze_source(by_id)
    assert not analyze_source(by_slug)
    assert not analyze_source(by_all)
    assert not analyze_source(file_wide)
    assert analyze_source(unknown)  # unknown tokens never silence anything


def test_donated_reuse_respects_frames_and_static_attrs():
    """Regression: a nested function's same-named parameter is a fresh binding
    (neither a reuse nor a rebind), and .shape/.dtype metadata reads of a
    donated array stay legal."""
    shadowed = (
        "import jax\n"
        "def train(step, params, grads):\n"
        "    f = jax.jit(step, donate_argnums=(0,))\n"
        "    out = f(grads)\n"
        "    def helper(grads):\n"
        "        return grads + 1\n"
        "    return out, helper\n"
    )
    assert not analyze_source(shadowed), analyze_source(shadowed)

    metadata = (
        "import jax\n"
        "def train(step, params, grads):\n"
        "    f = jax.jit(step, donate_argnums=(0,))\n"
        "    out = f(grads)\n"
        "    print(grads.shape)\n"
        "    return out\n"
    )
    assert not analyze_source(metadata), analyze_source(metadata)

    # ...but a shadow Store in a nested def must not mask a REAL reuse.
    masked = (
        "import jax\n"
        "def train(step, grads):\n"
        "    f = jax.jit(step, donate_argnums=(0,))\n"
        "    def helper():\n"
        "        grads = 0\n"
        "        return grads\n"
        "    out = f(grads)\n"
        "    return out + grads\n"
    )
    assert [f.rule_id for f in analyze_source(masked)] == ["TPU108"]


def test_closure_capture_ignores_array_accumulators():
    """Regression: `acc += x` may be a traced-array accumulator — only scalar
    counters (`i += 1`) and scalar-literal locals count as closure captures."""
    array_acc = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def make(xs):\n"
        "    total = jnp.zeros(())\n"
        "    for x in xs:\n"
        "        total += x\n"
        "    @jax.jit\n"
        "    def step(y):\n"
        "        return y + total\n"
        "    return step\n"
    )
    assert not analyze_source(array_acc), analyze_source(array_acc)

    counter = (
        "import jax\n"
        "def make(xs):\n"
        "    i = 0\n"
        "    for x in xs:\n"
        "        i += 1\n"
        "    @jax.jit\n"
        "    def step(y):\n"
        "        return y + i\n"
        "    return step\n"
    )
    assert [f.rule_id for f in analyze_source(counter)] == ["TPU105"]


def test_tpu114_router_variants():
    """The Router half of TPU114: an explicit max_queue=None and a missing
    default_deadline_s each flag; the bounded+deadlined spelling is clean; and
    a module with no real jax import is out of scope (host-side tooling that
    merely mentions a Router is not jit-adjacent serving code)."""
    hazard = (
        "import jax\n"
        "from accelerate_tpu.router import Router\n"
        "def fleet(model):\n"
        "    return Router(model, replicas=3, max_queue=None)\n"
    )
    findings = analyze_source(hazard)
    assert [f.rule_id for f in findings] == ["TPU114", "TPU114"]  # queue + deadline
    clean = hazard.replace(
        "max_queue=None", "max_queue=64, default_deadline_s=60.0"
    )
    assert not analyze_source(clean)
    no_jax = hazard.replace("import jax\n", "")
    assert not analyze_source(no_jax)


def test_tpu115_interpret_variant():
    """The kernel-call half of TPU115 (the flag fixture carries the
    attention_impl pin — one finding per fixture): a literal interpret=True on
    a Pallas attention kernel flags (the CPU-test shim on a production call
    site), interpret=None / omitted is clean, a threaded variable is clean,
    and a jax-free module is out of scope."""
    hazard = (
        "import jax\n"
        "from accelerate_tpu.ops.paged_attention import paged_decode_attention\n"
        "def attend(q, pk, pv, tbl, pos):\n"
        "    return paged_decode_attention(q, pk, pv, tbl, pos, interpret=True)\n"
    )
    findings = analyze_source(hazard)
    assert [f.rule_id for f in findings] == ["TPU115"]
    assert not analyze_source(hazard.replace("interpret=True", "interpret=None"))
    assert not analyze_source(hazard.replace("interpret=True", "interpret=interp"))
    assert not analyze_source(hazard.replace(", interpret=True", ""))
    assert not analyze_source(hazard.replace("import jax\n", ""))


def test_tpu115_impl_pin_variants():
    """A literal attention_impl="xla" flags wherever it is spelled — the
    engine, a config field, the seam — since every slot cache is a page pool;
    threading the impl as a variable (A/B harnesses) is clean."""
    hazard = (
        "import jax\n"
        "from accelerate_tpu.serving import ContinuousBatcher\n"
        "def engine(model):\n"
        '    return ContinuousBatcher(model, max_queue=8, attention_impl="xla")\n'
    )
    assert [f.rule_id for f in analyze_source(hazard)] == ["TPU115"]
    assert "fallback" not in analyze_source(hazard)[0].message
    assert not analyze_source(
        hazard.replace('attention_impl="xla"', "attention_impl=impl")
    )
    # The config-field spelling (dataclasses.replace / model configs) flags too.
    cfg = (
        "import jax\n"
        "import dataclasses\n"
        "def step_cfg(base):\n"
        '    return dataclasses.replace(base, decode_page_size=4, decode_attention_impl="xla")\n'
    )
    assert [f.rule_id for f in analyze_source(cfg)] == ["TPU115"]
    seam = (
        "import jax\n"
        "from accelerate_tpu.ops.attention import slot_cache_attention\n"
        "def attend(module, q, k, v, pos, tbl, ps):\n"
        "    return slot_cache_attention(module, q, k, v, 32, pos, page_table=tbl,\n"
        '                                page_size=ps, attention_impl="xla")\n'
    )
    assert [f.rule_id for f in analyze_source(seam)] == ["TPU115"]
    assert not analyze_source(seam.replace('attention_impl="xla"', "attention_impl=impl"))


def test_tpu116_worker_loop_variants():
    """The looped-recv half of TPU116 (the flag fixture carries the
    serve_worker pin — one finding per fixture): an unbounded recv_frame
    INSIDE a loop flags, a bounded one is clean, a one-shot recv outside any
    loop is clean (handshakes may use their own start timeout), an explicit
    heartbeat_deadline_s=None flags, and a jax-free module is out of scope."""
    hazard = (
        "import jax\n"
        "from accelerate_tpu.worker import recv_frame\n"
        "def pump(stream):\n"
        "    while True:\n"
        "        frame = recv_frame(stream)\n"
    )
    assert [f.rule_id for f in analyze_source(hazard)] == ["TPU116"]
    assert not analyze_source(
        hazard.replace("recv_frame(stream)", "recv_frame(stream, timeout_s=30.0)")
    )
    assert [f.rule_id for f in analyze_source(
        hazard.replace("recv_frame(stream)", "recv_frame(stream, timeout_s=None)")
    )] == ["TPU116"]
    one_shot = (
        "import jax\n"
        "from accelerate_tpu.worker import recv_frame\n"
        "def handshake(stream):\n"
        "    return recv_frame(stream, timeout_s=600.0)\n"
    )
    assert not analyze_source(one_shot)
    explicit_none = (
        "import jax\n"
        "from accelerate_tpu.worker import serve_worker\n"
        "def run(host, r, w):\n"
        "    return serve_worker(host, r, w, heartbeat_deadline_s=None)\n"
    )
    assert [f.rule_id for f in analyze_source(explicit_none)] == ["TPU116"]
    assert not analyze_source(hazard.replace("import jax\n", ""))


def test_tpu122_transport_variants():
    """The variants beyond the flag fixture's three hazards (dial, looped
    recv, bare reconnect loop): a timed dial is clean, an explicit
    timeout=None still flags, a module-wide settimeout legitimizes its recv
    loops (select-based framing arms deadlines away from the recv site), a
    recv with its own timeout_s is clean without settimeout, one-shot
    recv/reconnect outside any loop is clean, a budgeted reconnect attempt
    is clean, and socket-free or jax-free modules are out of scope."""
    dial = (
        "import socket\n"
        "import jax\n"
        "def connect(addr):\n"
        "    return socket.create_connection(addr)\n"
    )
    assert [f.rule_id for f in analyze_source(dial)] == ["TPU122"]
    assert not analyze_source(
        dial.replace("create_connection(addr)", "create_connection(addr, timeout=5.0)")
    )
    assert [f.rule_id for f in analyze_source(
        dial.replace("create_connection(addr)", "create_connection(addr, timeout=None)")
    )] == ["TPU122"]
    pump = (
        "import socket\n"
        "import jax\n"
        "def pump(sock):\n"
        "    while True:\n"
        "        if not sock.recv(4096):\n"
        "            break\n"
    )
    assert [f.rule_id for f in analyze_source(pump)] == ["TPU122"]
    armed = pump.replace(
        "def pump(sock):\n", "def pump(sock):\n    sock.settimeout(5.0)\n"
    )
    assert not analyze_source(armed)
    # a duck-typed transport recv carrying its own deadline needs no settimeout
    assert not analyze_source(
        pump.replace("sock.recv(4096)", "sock.recv(4096, timeout_s=5.0)")
    )
    one_shot = (
        "import socket\n"
        "import jax\n"
        "def peek(sock):\n"
        "    return sock.recv(4096)\n"
    )
    assert not analyze_source(one_shot)
    heal = (
        "import socket\n"
        "import jax\n"
        "def heal(link):\n"
        "    while True:\n"
        "        try:\n"
        "            return link.reconnect()\n"
        "        except OSError:\n"
        "            continue\n"
    )
    assert [f.rule_id for f in analyze_source(heal)] == ["TPU122"]
    assert not analyze_source(
        heal.replace("link.reconnect()", "link.reconnect(timeout_s=2.0)")
    )
    assert not analyze_source(pump.replace("import socket\n", ""))
    assert not analyze_source(pump.replace("import jax\n", ""))


def test_tpu117_variants():
    """The variants beyond the flag fixture's k_scale literal (one finding
    per fixture): a v_scale literal flags, a threaded array variable is
    clean, an int literal flags, a scale kwarg on an unrelated function is
    out of scope (no false positives on generic `k_scale=` spellings),
    kv_cache_dtype literals off the supported set flag in both the engine and
    config spellings, supported literals and variables are clean, and a
    jax-free module is out of scope."""
    hazard = (
        "import jax\n"
        "from accelerate_tpu.ops.paged_attention import paged_verify_attention\n"
        "def attend(q, pk, pv, tbl, pos, ks):\n"
        "    return paged_verify_attention(q, pk, pv, tbl, pos, k_scale=ks, v_scale=0.01)\n"
    )
    assert [f.rule_id for f in analyze_source(hazard)] == ["TPU117"]
    assert not analyze_source(hazard.replace("v_scale=0.01", "v_scale=vs"))
    assert [f.rule_id for f in analyze_source(
        hazard.replace("v_scale=0.01", "v_scale=1")
    )] == ["TPU117"]
    unrelated = (
        "import jax\n"
        "def tune(plotter):\n"
        "    return plotter.draw(k_scale=0.5)\n"
    )
    assert not analyze_source(unrelated)
    engine = (
        "import jax\n"
        "from accelerate_tpu.serving import ContinuousBatcher\n"
        "def build(model):\n"
        '    return ContinuousBatcher(model, max_queue=8, kv_cache_dtype="int4")\n'
    )
    assert [f.rule_id for f in analyze_source(engine)] == ["TPU117"]
    assert not analyze_source(engine.replace('"int4"', '"fp8_e4m3"'))
    assert not analyze_source(engine.replace('"int4"', "dtype_flag"))
    cfg = (
        "import jax\n"
        "import dataclasses\n"
        "def step_cfg(base):\n"
        '    return dataclasses.replace(base, decode_kv_cache_dtype="fp16")\n'
    )
    assert [f.rule_id for f in analyze_source(cfg)] == ["TPU117"]
    assert not analyze_source(cfg.replace('"fp16"', '"bf16"'))
    assert not analyze_source(hazard.replace("import jax\n", ""))


def test_tpu118_variants():
    """Beyond the flag fixture's bare device_put (one finding per fixture):
    a raw-device placement flags, a None placement flags, a NamedSharding /
    derived-shardings / unknown-name placement is clean (precomputed sharding
    pytrees get the benefit of the doubt), a module with NO "model"-axis mesh
    is out of scope however it places things, a Mesh(..., ("model",)) literal
    counts as mesh-spanning the same as serving_tp_mesh, and a jax-free
    module is out of scope."""
    hazard = (
        "import jax\n"
        "from accelerate_tpu.parallel.sharding import serving_tp_mesh\n"
        "def place(params):\n"
        "    mesh = serving_tp_mesh(4)\n"
        "    return jax.device_put(params, jax.devices()[0])\n"
    )
    assert [f.rule_id for f in analyze_source(hazard)] == ["TPU118"]
    assert [f.rule_id for f in analyze_source(
        hazard.replace("jax.devices()[0]", "None")
    )] == ["TPU118"]
    assert not analyze_source(
        hazard.replace("jax.devices()[0]", "NamedSharding(mesh, spec)")
    )
    assert not analyze_source(
        hazard.replace("jax.devices()[0]", "derive_tp_param_shardings(params, mesh, rules)")
    )
    assert not analyze_source(hazard.replace("jax.devices()[0]", "shardings"))
    # No "model"-axis mesh in the module: ordinary single-device placement.
    no_mesh = (
        "import jax\n"
        "def place(params):\n"
        "    return jax.device_put(params)\n"
    )
    assert not analyze_source(no_mesh)
    # A literal Mesh with a "model" axis counts as mesh-spanning too.
    literal_mesh = (
        "import jax\n"
        "from jax.sharding import Mesh\n"
        "def place(params, devices):\n"
        '    mesh = Mesh(devices, ("model",))\n'
        "    return jax.device_put(params)\n"
    )
    assert [f.rule_id for f in analyze_source(literal_mesh)] == ["TPU118"]
    assert not analyze_source(literal_mesh.replace('("model",)', '("data",)'))
    assert not analyze_source(hazard.replace("import jax\n", ""))


def test_tpu119_variants():
    """Beyond the flag fixture's dead table entry (one finding per fixture):
    a live entry whose tokens connect to flax submodule names is clean, an
    f-string name part counts as evidence, an all-generic pattern is skipped
    (can't be judged statically), a literal string-axis PartitionSpec in a
    flax model module flags while the empty PartitionSpec() does not, and
    modules without flax (or without jax) are out of scope however their
    tables look."""
    base = (
        "import jax\n"
        "import flax.linen as nn\n"
        "RULES_SHARDING_RULES = [(r\"{pattern}\", (None, \"model\"))]\n"
        "class Toy(nn.Module):\n"
        "    @nn.compact\n"
        "    def __call__(self, x):\n"
        "        return nn.Dense(4, name=\"wq\")(x)\n"
    )
    dead = base.replace("{pattern}", "query_proj/kernel")
    assert [f.rule_id for f in analyze_source(dead)] == ["TPU119"]
    assert not analyze_source(base.replace("{pattern}", "wq/kernel"))
    # f-string submodule names vouch for the pattern's tokens.
    fstring = (
        "import jax\n"
        "import flax.linen as nn\n"
        "TOY_SHARDING_RULES = [(r\"block_\\d+/kernel\", (None, \"model\"))]\n"
        "class Toy(nn.Module):\n"
        "    @nn.compact\n"
        "    def __call__(self, x):\n"
        "        for i in range(2):\n"
        "            x = nn.Dense(4, name=f\"block_{i}\")(x)\n"
        "        return x\n"
    )
    assert not analyze_source(fstring)
    # All-generic patterns (kernel/embedding/bias...) carry no module identity.
    assert not analyze_source(base.replace("{pattern}", "kernel$"))
    # A literal string-axis PartitionSpec outside the table flags; the empty
    # replicated spec does not.
    literal = (
        "import jax\n"
        "import flax.linen as nn\n"
        "from jax.sharding import PartitionSpec\n"
        "def place():\n"
        "    return PartitionSpec(None, \"model\")\n"
    )
    assert [f.rule_id for f in analyze_source(literal)] == ["TPU119"]
    assert not analyze_source(literal.replace("PartitionSpec(None, \"model\")", "PartitionSpec()"))
    # Tuple-nested axis literals flag too.
    assert [f.rule_id for f in analyze_source(
        literal.replace("PartitionSpec(None, \"model\")", "PartitionSpec((\"data\", \"fsdp\"))")
    )] == ["TPU119"]
    # No flax import: not a model module — rule tables and specs are the
    # derivation layer's business (parallel/sharding.py spells both).
    assert not analyze_source(dead.replace("import flax.linen as nn\n", ""))
    assert not analyze_source(literal.replace("import flax.linen as nn\n", ""))
    # No jax import: out of scope entirely.
    assert not analyze_source(dead.replace("import jax\n", ""))


def test_tpu120_variants():
    """Beyond the flag fixture's bare device_put (one finding per fixture):
    a raw-device placement flags, an explicit NamedSharding(mesh,
    PartitionSpec()) — replicate spelled out — flags, a derived/unknown-name
    placement is clean (precomputed sharding pytrees get the benefit of the
    doubt), a non-opt-state operand is out of scope (that's TPU118's beat,
    and only on "model" meshes), a module with NO data-axis mesh is out of
    scope however it places moments, ParallelismConfig(data=...) and
    Mesh(..., ("data",...)) both count as data-mesh evidence, and a jax-free
    module is out of scope."""
    hazard = (
        "import jax\n"
        "from accelerate_tpu.utils import ParallelismConfig\n"
        "def restore(tx, params):\n"
        "    cfg = ParallelismConfig(data=-1)\n"
        "    opt_state = tx.init(params)\n"
        "    return cfg, jax.device_put(opt_state)\n"
    )
    assert [f.rule_id for f in analyze_source(hazard)] == ["TPU120"]
    assert [f.rule_id for f in analyze_source(
        hazard.replace("jax.device_put(opt_state)",
                       "jax.device_put(opt_state, jax.devices()[0])")
    )] == ["TPU120"]
    # Replicate spelled out: every PartitionSpec in the placement is empty.
    assert [f.rule_id for f in analyze_source(
        hazard.replace("jax.device_put(opt_state)",
                       "jax.device_put(opt_state, NamedSharding(mesh, PartitionSpec()))")
    )] == ["TPU120"]
    # A sharded spec, a derived pytree, or an unknown name: clean.
    assert not analyze_source(
        hazard.replace("jax.device_put(opt_state)",
                       "jax.device_put(opt_state, NamedSharding(mesh, PartitionSpec(\"data\")))")
    )
    assert not analyze_source(
        hazard.replace(
            "jax.device_put(opt_state)",
            "jax.device_put(opt_state, derive_opt_state_shardings(shapes, mesh, "
            "rules=rules, opt_rules=plan.opt_rules))",
        )
    )
    assert not analyze_source(
        hazard.replace("jax.device_put(opt_state)",
                       "jax.device_put(opt_state, opt_shardings)")
    )
    # Not an optimizer-state operand: TPU120 stays quiet (a bare params
    # placement on a data-only mesh is plain data parallelism, not ZeRO's
    # business — and TPU118 only polices "model"-axis meshes).
    assert not analyze_source(
        hazard.replace("opt_state = tx.init(params)\n", "")
        .replace("jax.device_put(opt_state)", "jax.device_put(params)")
    )
    # No data-axis mesh anywhere in the module: out of scope.
    assert not analyze_source(
        hazard.replace(
            "    cfg = ParallelismConfig(data=-1)\n", "    cfg = None\n"
        )
    )
    # A literal Mesh with a "data" axis counts as data-mesh evidence too.
    mesh_hazard = (
        "import jax\n"
        "from jax.sharding import Mesh\n"
        "def restore(adam_state, devices):\n"
        '    mesh = Mesh(devices, ("data",))\n'
        "    return jax.device_put(adam_state, optimizer_state_placement)\n"
    )
    assert not analyze_source(mesh_hazard)  # named placement: benefit of the doubt
    assert [f.rule_id for f in analyze_source(
        mesh_hazard.replace(", optimizer_state_placement", "")
    )] == ["TPU120"]
    assert not analyze_source(
        mesh_hazard.replace(", optimizer_state_placement", "")
        .replace('("data",)', '("stage",)')
    )
    assert not analyze_source(hazard.replace("import jax\n", ""))


def test_tpu121_variants():
    """Beyond the flag fixture's device_get (one finding per fixture): the
    numpy coercion and .block_until_ready() spellings flag too, jnp.asarray
    stays on device and is clean, a non-handoff operand is out of scope, a
    module with no pipeline-mesh evidence is out of scope however it moves
    carries, ParallelismConfig(pipeline=...) and Mesh(..., ("pipeline",))
    both count as pipeline-mesh evidence, and a jax-free module is out of
    scope."""
    hazard = (
        "import jax\n"
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "from accelerate_tpu.parallel import slice_mesh\n"
        "def handoff(mesh, fwd, params, batch):\n"
        '    subs = slice_mesh(mesh, "pipeline")\n'
        "    carry = fwd(params, batch)\n"
        "    return subs, jax.device_get(carry)\n"
    )
    assert [f.rule_id for f in analyze_source(hazard)] == ["TPU121"]
    # The silent device_get: numpy coercion of the carry.
    assert [f.rule_id for f in analyze_source(
        hazard.replace("jax.device_get(carry)", "np.asarray(carry)")
    )] == ["TPU121"]
    assert [f.rule_id for f in analyze_source(
        hazard.replace("jax.device_get(carry)", "np.array(carry)")
    )] == ["TPU121"]
    # Blocking the schedule on the handoff: both spellings.
    assert [f.rule_id for f in analyze_source(
        hazard.replace("jax.device_get(carry)", "carry.block_until_ready()")
    )] == ["TPU121"]
    assert [f.rule_id for f in analyze_source(
        hazard.replace("jax.device_get(carry)", "jax.block_until_ready(carry)")
    )] == ["TPU121"]
    # jnp.asarray stays on device — not a host hop.
    assert not analyze_source(
        hazard.replace("jax.device_get(carry)", "jnp.asarray(carry)")
    )
    # Cotangents and activations are handoff labels too.
    assert [f.rule_id for f in analyze_source(
        hazard.replace("carry", "g_out")
    )] == ["TPU121"]
    # A non-handoff operand (checkpoint pull of merged params): out of scope.
    assert not analyze_source(
        hazard.replace("jax.device_get(carry)", "jax.device_get(merged)")
    )
    # No pipeline-mesh evidence in the module: out of scope.
    assert not analyze_source(
        hazard.replace('    subs = slice_mesh(mesh, "pipeline")\n', "    subs = None\n")
    )
    # ParallelismConfig(pipeline=...) and a literal Mesh with a "pipeline"
    # axis both count as pipeline-mesh evidence.
    for spelling in (
        "    subs = ParallelismConfig(pipeline=2)\n",
        '    subs = Mesh(devices, ("data", "pipeline"))\n',
    ):
        assert [f.rule_id for f in analyze_source(
            hazard.replace('    subs = slice_mesh(mesh, "pipeline")\n', spelling)
        )] == ["TPU121"]
    assert not analyze_source(
        hazard.replace("import jax\n", "").replace("import jax.numpy as jnp\n", "")
        .replace("jax.device_get(carry)", "np.asarray(carry)")
    )


def test_analyze_paths_walks_the_tree():
    findings, scanned = analyze_paths([str(SAMPLES)])
    assert scanned >= 2 * len(RULES) + 1  # flag + clean per rule + suppressed.py
    assert {f.rule_id for f in findings} == set(RULE_IDS)
    per_rule = {rid: [f for f in findings if f.rule_id == rid] for rid in RULE_IDS}
    assert all(len(v) == 1 for v in per_rule.values()), {
        k: len(v) for k, v in per_rule.items() if len(v) != 1
    }
    assert all(f.file.endswith("_flag.py") for f in findings)


def test_analyze_paths_missing_path():
    with pytest.raises(FileNotFoundError):
        analyze_paths(["/nonexistent/really-not-here"])


# ---------------------------------------------------------------------- CLI
def _run_cli(argv, capsys):
    from accelerate_tpu.commands.accelerate_cli import get_command_parser

    parser = get_command_parser()
    args = parser.parse_args(argv)
    with pytest.raises(SystemExit) as excinfo:
        args.func(args)
    out = capsys.readouterr()
    return excinfo.value.code, out.out, out.err


def test_cli_json_round_trip(capsys):
    code, out, _ = _run_cli(["analyze", str(SAMPLES), "--json"], capsys)
    assert code == 1  # error-severity findings exist in the flag fixtures
    payload = json.loads(out)
    assert payload["version"] == 1
    assert payload["files_scanned"] >= 2 * len(RULES)
    assert {f["rule"] for f in payload["findings"]} == set(RULE_IDS)
    sample = payload["findings"][0]
    assert set(sample) == {"file", "line", "col", "rule", "slug", "severity", "message", "fixit"}
    assert payload["counts"]["error"] >= 1 and payload["counts"]["warn"] >= 1


def test_cli_exit_codes(capsys, tmp_path):
    # clean tree -> 0
    clean = tmp_path / "clean.py"
    clean.write_text("def f(x):\n    return x + 1\n")
    code, _, _ = _run_cli(["analyze", str(tmp_path)], capsys)
    assert code == 0

    # warn-only tree: default threshold passes, --fail-on warn gates
    warn_only = tmp_path / "warn.py"
    warn_only.write_text(SAMPLES.joinpath("tpu111_flag.py").read_text())
    code, _, _ = _run_cli(["analyze", str(warn_only)], capsys)
    assert code == 0
    code, _, _ = _run_cli(["analyze", str(warn_only), "--fail-on", "warn"], capsys)
    assert code == 1

    # error finding -> 1 at the default threshold
    err = tmp_path / "err.py"
    err.write_text(SAMPLES.joinpath("tpu101_flag.py").read_text())
    code, _, _ = _run_cli(["analyze", str(err)], capsys)
    assert code == 1

    # bad path -> usage error 2
    code, _, errout = _run_cli(["analyze", str(tmp_path / "missing")], capsys)
    assert code == 2
    assert "no such file" in errout


def test_cli_list_rules(capsys):
    code, out, _ = _run_cli(["analyze", "--list-rules", "."], capsys)
    assert code == 0
    for rule in RULES:
        assert rule.id in out and rule.slug in out
