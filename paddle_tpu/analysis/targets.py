"""tpulint flagship analysis targets.

The concrete callables the CI gate analyzes every round — small-config
builds of exactly the programs that carry the repo's numbers:

- ``gpt-eager``   GPTForCausalLM forward + loss through the framework tape
                  (op-dtype trace -> TR001 AMP cross-check);
- ``bert-eager``  BertModel forward, same trace;
- ``gpt-spmd``    the hybrid-parallel train step (jaxpr walk + donation);
- ``serving-unified``  the unified ragged prefill+decode step jit
                  (jaxpr walk + donation audit of the page pools —
                  the ONE program the serving path replays);
- ``serving-quant``  the round-10 quantized serving step: the
                  int8-weight/int8-KV unified step (jaxpr walk incl. the
                  JX001 scale-promotion audit, donation of pools AND
                  scale planes);
- ``serving-spmd``  the round-11 mesh-sharded serving step over
                  ``Mesh(("mp",))``: the sharded quantized unified step
                  (jaxpr walk through the shard_map body, JX005 donation
                  audit over the head-sharded pools and scale planes);
- ``serving-spec``  the round-12 speculative unified step
                  (``spec_k > 0``: verify rows + fused accept epilogue),
                  fp and int8-weight/int8-KV variants — jaxpr walk of the
                  draft-token verify/accept program and the JX005
                  donation audit over the pools and scale planes at their
                  SHIFTED positions (the spec_len input precedes them);
- ``train-dpquant``  the round-14 comm-quant dp train step: per-replica
                  gradients stacked under vmap, the int8 quantized ring
                  allreduce (quantize -> GSPMD-roll hop -> deterministic
                  requantization) replacing the implicit fp allreduce —
                  jaxpr walk incl. the JX001 scale-promotion audit on the
                  dequant path (block scales multiplying into the decode
                  must never widen it to f64) + the JX005 donation audit
                  of (params, momentum);
- ``serving-spec-model``  the round-19 model-draft speculative serving
                  pair: the truncated-layer SELF-DRAFT jit
                  (``build_draft_step`` — the first ``draft_layers``
                  stacks of the same serving params at the chunk-1 chain
                  geometry, its pools donated like any serving step) and
                  the spec-async unified step (``spec_k > 0`` with the
                  feedback carry LIVE on a verify row — the behind-by-one
                  dispatch shape) with the JX005 donation audit at the
                  spec-shifted pool positions;
- ``serving-async``  the round-13 feedback-coupled unified step as the
                  async double-buffered engine drives it: a LIVE
                  ``feedback`` mask routing a decode lane's input token
                  from the previous step's ``prev_toks`` carry, the
                  on-device sample-key fold, and the JX005 donation
                  audit at the feedback-shifted pool positions — a
                  dispatch-ahead step that silently stopped aliasing its
                  pools would double cache memory exactly when two steps
                  are in flight;
- ``serving-tiered``  the round-21 tiered KV cache's batched restore
                  scatter (``batched_import_rows`` — the ONE donated
                  ``pages.at[:, pg, row].set(..., mode="drop")`` jit a
                  host-tier restore round or transfer tick issues per
                  (K, V, scale) plane): jaxpr walk over BOTH plane
                  geometries (the 5D fp and int8 pools, the 4D fp32
                  scale plane) + the JX005 donation audit of the pool
                  at argument 0 — an undonated restore would copy the
                  whole HBM pool per plane per round, exactly the
                  eager per-page cost the batched path exists to
                  retire.

Configs are tiny (seconds on CPU; the analysis is abstract — eval_shape /
make_jaxpr, no FLOPs run) but structurally identical to the flagship
shapes: every scan/remat/constraint/donation the real programs use is in
the traced jaxpr.

Round 23 adds COST CERTIFICATION on top of the hazard walk: targets with
an entry in :mod:`.contracts` re-trace their step with ``use_kernel=True``
(the pallas path the TPU runs) and gate the static JX007 hbm model, the
JX008 VMEM footprints and the JX009 collective inventory against the
committed table; ``train-dpquant`` additionally compiles and audits the HLO
wire (fp all-reduce ban + s8 payloads).
"""
from __future__ import annotations

from .contracts import cost_certify, hlo_certify
from .findings import Finding
from .jaxpr_checks import (OpDtypeTrace, analyze_jaxpr, check_donation,
                           trace_callable)


def analyze_gpt_eager() -> list[Finding]:
    import numpy as np

    import paddle_tpu as paddle
    from ..models.gpt import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, 128, (2, 8)).astype(np.int64))
    with OpDtypeTrace() as tr:
        loss = model(ids, labels=ids)
        del loss
    return tr.findings("gpt-eager")


def analyze_bert_eager() -> list[Finding]:
    import numpy as np

    import paddle_tpu as paddle
    from ..models.bert import BERT_CONFIGS, BertModel

    paddle.seed(0)
    model = BertModel(BERT_CONFIGS["bert-tiny"])
    model.eval()  # dropout off: audit the inference dtype flow
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, 1024, (2, 8)).astype(np.int64))
    with OpDtypeTrace() as tr:
        model(ids)
    return tr.findings("bert-eager")


def analyze_gpt_spmd() -> list[Finding]:
    import jax

    from ..models.gpt import GPTConfig
    from ..models.gpt_spmd import build_spmd_train_step, make_mesh

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32)
    mesh = make_mesh(len(jax.devices()))
    step, params, mom, (ids, labels) = build_spmd_train_step(
        cfg, mesh, batch_size=4, seq_len=32)
    closed = trace_callable(step, params, mom, ids, labels)
    findings = analyze_jaxpr(closed, "gpt-spmd-step")
    # the builder donates (params, momentum); both must alias outputs
    findings += check_donation(step, (params, mom, ids, labels), (0, 1),
                               "gpt-spmd-step")
    return findings


def analyze_train_dpquant() -> list[Finding]:
    """Round-14 quantized-dp training: the train step with the implicit
    GSPMD gradient allreduce replaced by the explicit int8 quantized ring
    (``build_spmd_train_step(comm_quant="int8")`` over a dp=2 mesh). The
    jaxpr walk covers the stacked per-replica grad computation, every
    quantize/roll/dequantize hop and the int8 distribution phase — JX001
    is the scale-promotion audit (fp32 block scales multiplying into the
    decode must never widen the chain to f64) and JX005 the donation
    audit of (params, momentum) through the new step body."""
    import numpy as np

    import jax
    from jax.sharding import Mesh

    from ..models.gpt import GPTConfig
    from ..models.gpt_spmd import build_spmd_train_step

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32)
    if len(jax.devices()) < 2:
        # comm_quant is INERT at dp=1 (build_spmd_train_step only takes
        # the quantized path for dp > 1): a dp=1 fallback would audit the
        # plain fp step and report a false-green empty baseline. The CLI
        # gate and the test suite both force an 8-device virtual mesh.
        raise RuntimeError(
            "train-dpquant needs >= 2 devices (the quantized ring is "
            "inert at dp=1); run under the forced virtual CPU mesh like "
            "the `python -m paddle_tpu.analysis` gate")
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1, 1),
                ("dp", "pp", "mp"))
    step, params, mom, (ids, labels) = build_spmd_train_step(
        cfg, mesh, batch_size=4, seq_len=32, comm_quant="int8")
    closed = trace_callable(step, params, mom, ids, labels)
    findings = analyze_jaxpr(closed, "train-dpquant-step")
    # the builder donates (params, momentum); both must alias outputs
    findings += check_donation(step, (params, mom, ids, labels), (0, 1),
                               "train-dpquant-step")
    # round 23: the wire contract is only visible in COMPILED HLO (the
    # ring's quantize->roll hops become collective-permutes at partition
    # time) — compile and audit: no gradient-sized fp all-reduce, s8
    # payloads actually on the wire
    findings += hlo_certify("train-dpquant-step", step,
                            (params, mom, ids, labels), mesh=mesh)
    return findings


def analyze_serving_unified() -> list[Finding]:
    import numpy as np

    import jax.numpy as jnp

    import paddle_tpu as paddle
    from ..inference.kv_cache import KVCacheManager
    from ..models.gpt import (GPTConfig, GPTForCausalLM, build_unified_step,
                              serving_params)

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    params = serving_params(model)
    page_size, chunk, b = 8, 4, 2
    budget = b + chunk
    mgr = KVCacheManager(cfg.num_layers, cfg.num_heads, cfg.head_dim,
                         num_pages=2 * b * (cfg.max_seq_len // page_size),
                         max_batch=b, max_seq_len=cfg.max_seq_len,
                         page_size=page_size, dtype=jnp.float32,
                         enable_prefix_cache=True)
    rng = np.random.RandomState(0)
    for _ in range(b):
        mgr.admit_prefix([int(x) for x in rng.randint(0, 128, (8,))])
    # a mixed step: slot 0 decodes 1 token, slot 1 feeds a prefill chunk
    tok_ids = jnp.asarray(rng.randint(0, 128, (budget,)), jnp.int32)
    tok_slot = jnp.asarray([0] + [1] * chunk + [-1] * (budget - 1 - chunk),
                           jnp.int32)
    tok_pos = jnp.asarray([0] + list(range(chunk))
                          + [0] * (budget - 1 - chunk), jnp.int32)
    q_lens = jnp.asarray([1, chunk], jnp.int32)
    kv_lens = mgr.seq_lens_device() * 0
    last_idx = jnp.asarray([0, chunk], jnp.int32)
    no_cow = jnp.full((b,), mgr.num_pages, jnp.int32)
    feedback = jnp.zeros((budget,), jnp.int32)
    prev_toks = jnp.zeros((b,), jnp.int32)
    emit = jnp.asarray([1, 0], jnp.int32)
    produced = jnp.zeros((b,), jnp.int32)
    keys = jnp.zeros((b, 2), jnp.uint32)
    temp = jnp.asarray([0.0, 0.8], jnp.float32)
    top_k = jnp.asarray([0, 40], jnp.int32)
    top_p = jnp.asarray([1.0, 0.9], jnp.float32)

    step = build_unified_step(cfg, page_size, chunk)
    args = (params, tok_ids, tok_slot, tok_pos, q_lens, kv_lens, last_idx,
            feedback, prev_toks, emit, produced,
            mgr.k_pages, mgr.v_pages, mgr.page_table_device(), no_cow,
            no_cow, keys, temp, top_k, top_p)
    findings = analyze_jaxpr(trace_callable(step, *args),
                             "serving-unified-step")
    # the builder donates the K/V page pools; both must alias outputs
    findings += check_donation(step, args, (11, 12), "serving-unified-step")
    # round 23: cost-certify the KERNEL build (use_kernel=True forces the
    # pallas path the TPU runs, so JX008 sees the real launch geometry)
    kstep = build_unified_step(cfg, page_size, chunk, use_kernel=True)
    findings += cost_certify("serving-unified-step",
                             trace_callable(kstep, *args), params=params,
                             cache=mgr)
    return findings


def analyze_serving_quant() -> list[Finding]:
    """Round-10 quantized serving: the int8-weight + int8-KV unified step.
    The jaxpr walk's JX001 leg is
    the scale-promotion audit — per-group scales multiplying into the
    compute must never widen it to f64 (and the donation audit covers the
    int8 pools AND their scale planes)."""
    import numpy as np

    import jax.numpy as jnp

    import paddle_tpu as paddle
    from ..inference.kv_cache import KVCacheManager
    from ..inference.quantize import quantize_serving_params
    from ..models.gpt import (GPTConfig, GPTForCausalLM, build_unified_step,
                              serving_params)

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    params = quantize_serving_params(serving_params(model), "int8",
                                     group_size=16)
    page_size, chunk, b = 8, 4, 2
    budget = b + chunk
    rng = np.random.RandomState(0)
    findings: list[Finding] = []

    # int8-weight + int8-KV unified step (quantize-on-write + scale planes)
    qmgr = KVCacheManager(cfg.num_layers, cfg.num_heads, cfg.head_dim,
                          num_pages=2 * b * (cfg.max_seq_len // page_size),
                          max_batch=b, max_seq_len=cfg.max_seq_len,
                          page_size=page_size, dtype=jnp.float32,
                          quantize_kv=True)
    tok_ids = jnp.asarray(rng.randint(0, 128, (budget,)), jnp.int32)
    tok_slot = jnp.asarray([0] + [1] * chunk + [-1] * (budget - 1 - chunk),
                           jnp.int32)
    tok_pos = jnp.asarray([0] + list(range(chunk))
                          + [0] * (budget - 1 - chunk), jnp.int32)
    q_lens = jnp.asarray([1, chunk], jnp.int32)
    kv_lens = qmgr.seq_lens_device()
    last_idx = jnp.asarray([0, chunk], jnp.int32)
    no_cow = jnp.full((b,), qmgr.num_pages, jnp.int32)
    feedback = jnp.zeros((budget,), jnp.int32)
    prev_toks = jnp.zeros((b,), jnp.int32)
    emit = jnp.asarray([1, 0], jnp.int32)
    produced = jnp.zeros((b,), jnp.int32)
    keys = jnp.zeros((b, 2), jnp.uint32)
    temp = jnp.asarray([0.0, 0.8], jnp.float32)
    top_k = jnp.asarray([0, 40], jnp.int32)
    top_p = jnp.asarray([1.0, 0.9], jnp.float32)
    step = build_unified_step(cfg, page_size, chunk, kv_quant=True)
    args = (params, tok_ids, tok_slot, tok_pos, q_lens, kv_lens, last_idx,
            feedback, prev_toks, emit, produced,
            qmgr.k_pages, qmgr.v_pages, qmgr.k_scales, qmgr.v_scales,
            qmgr.page_table_device(), no_cow, no_cow, keys, temp, top_k,
            top_p)
    findings += analyze_jaxpr(trace_callable(step, *args),
                              "serving-quant-unified-step")
    # pools AND scale planes donate; all four must alias outputs
    findings += check_donation(step, args, (11, 12, 13, 14),
                               "serving-quant-unified-step")
    # round 23: cost-certify the kernel build (static hbm vs the bench
    # model with int8 pools + scale planes, kernel VMEM budgets)
    kstep = build_unified_step(cfg, page_size, chunk, kv_quant=True,
                               use_kernel=True)
    findings += cost_certify("serving-quant-unified-step",
                             trace_callable(kstep, *args), params=params,
                             cache=qmgr)
    return findings


def analyze_serving_spmd() -> list[Finding]:
    """Round-11 multi-chip SPMD serving: the int8-weight + int8-KV unified
    step sharded over ``Mesh(("mp",))``. The jaxpr walk recurses the
    shard_map body (collectives included); the JX005 donation audit
    covers the HEAD-SHARDED pools AND scale planes — a sharded donation
    that stops aliasing would double per-chip cache memory exactly where
    capacity is tightest."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from ..distributed.mesh import make_serving_mesh
    from ..inference.kv_cache import KVCacheManager
    from ..inference.quantize import quantize_serving_params
    from ..models.gpt import (GPTConfig, GPTForCausalLM, build_unified_step,
                              serving_params, shard_serving_params)

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32)
    mesh = make_serving_mesh(2 if len(jax.devices()) >= 2 else 1)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    page_size, chunk, b = 8, 4, 2
    budget = b + chunk
    rng = np.random.RandomState(0)
    findings: list[Finding] = []

    # sharded int8-weight + int8-KV unified step: head-sharded pools AND
    # scale planes through the donation audit
    q_params = shard_serving_params(
        quantize_serving_params(serving_params(model), "int8",
                                group_size=16), mesh, cfg)
    qmgr = KVCacheManager(cfg.num_layers, cfg.num_heads, cfg.head_dim,
                          num_pages=2 * b * (cfg.max_seq_len // page_size),
                          max_batch=b, max_seq_len=cfg.max_seq_len,
                          page_size=page_size, dtype=jnp.float32,
                          quantize_kv=True, mesh=mesh)
    tok_ids = jnp.asarray(rng.randint(0, 128, (budget,)), jnp.int32)
    tok_slot = jnp.asarray([0] + [1] * chunk + [-1] * (budget - 1 - chunk),
                           jnp.int32)
    tok_pos = jnp.asarray([0] + list(range(chunk))
                          + [0] * (budget - 1 - chunk), jnp.int32)
    q_lens = jnp.asarray([1, chunk], jnp.int32)
    kv_lens = qmgr.seq_lens_device()
    last_idx = jnp.asarray([0, chunk], jnp.int32)
    no_cow = jnp.full((b,), qmgr.num_pages, jnp.int32)
    feedback = jnp.zeros((budget,), jnp.int32)
    prev_toks = jnp.zeros((b,), jnp.int32)
    emit = jnp.asarray([1, 0], jnp.int32)
    produced = jnp.zeros((b,), jnp.int32)
    keys = jnp.zeros((b, 2), jnp.uint32)
    temp = jnp.asarray([0.0, 0.8], jnp.float32)
    top_k = jnp.asarray([0, 40], jnp.int32)
    top_p = jnp.asarray([1.0, 0.9], jnp.float32)
    step = build_unified_step(cfg, page_size, chunk, kv_quant=True,
                              mesh=mesh)
    args = (q_params, tok_ids, tok_slot, tok_pos, q_lens, kv_lens, last_idx,
            feedback, prev_toks, emit, produced,
            qmgr.k_pages, qmgr.v_pages, qmgr.k_scales, qmgr.v_scales,
            qmgr.page_table_device(), no_cow, no_cow, keys, temp, top_k,
            top_p)
    closed = trace_callable(step, *args)
    findings += analyze_jaxpr(closed, "serving-spmd-unified-step")
    findings += check_donation(step, args, (11, 12, 13, 14),
                               "serving-spmd-unified-step")
    # round 23: cost-certify the sharded step — the "only 2L row-parallel
    # psums" claim becomes the committed JX009 inventory, and the static
    # hbm model runs at mp=2 (contract geometry; inert on a 1-device env
    # where the mesh degenerates)
    if mesh.devices.size == 2:
        findings += cost_certify("serving-spmd-unified-step", closed,
                                 params=q_params, cache=qmgr)
    return findings


def analyze_serving_spec() -> list[Finding]:
    """Round-12 speculative serving: the unified step built with
    ``spec_k > 0`` — a decode lane feeding its last context token plus
    draft tokens as verify rows, the fused accept epilogue emitting
    ``out_ids[b, k+1]`` / ``n_emit[b]``. Both the fp and the
    int8-weight + int8-KV variants walk through the jaxpr checks, and the
    JX005 donation audit covers the pools (and scale planes) at their
    spec-shifted argument positions — a speculative step that silently
    stopped aliasing its pools would double cache memory exactly when the
    verify rows make the step its largest."""
    import numpy as np

    import jax.numpy as jnp

    import paddle_tpu as paddle
    from ..inference.kv_cache import KVCacheManager
    from ..inference.quantize import quantize_serving_params
    from ..models.gpt import (GPTConfig, GPTForCausalLM, build_unified_step,
                              serving_params)

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    fp_params = serving_params(model)
    q_params = quantize_serving_params(serving_params(model), "int8",
                                       group_size=16)
    page_size, chunk, b, spec_k = 8, 8, 2, 3
    budget = b * (1 + spec_k) + chunk
    rng = np.random.RandomState(0)
    findings: list[Finding] = []

    def spec_args(params, mgr):
        for _ in range(b):
            mgr.admit_prefix([int(x) for x in rng.randint(0, 128, (8,))])
        # a mixed step: slot 0 decodes with 3 verify rows (1 + 2 drafts),
        # slot 1 feeds a plain prefill chunk
        tok_ids = jnp.asarray(rng.randint(0, 128, (budget,)), jnp.int32)
        tok_slot = jnp.asarray(
            [0] * 3 + [1] * chunk + [-1] * (budget - 3 - chunk), jnp.int32)
        tok_pos = jnp.asarray(
            list(range(8, 11)) + list(range(chunk))
            + [0] * (budget - 3 - chunk), jnp.int32)
        q_lens = jnp.asarray([3, chunk], jnp.int32)
        kv_lens = jnp.asarray([8, 0], jnp.int32)
        last_idx = jnp.asarray([0, 3 + chunk - 1], jnp.int32)
        spec_len = jnp.asarray([2, 0], jnp.int32)
        no_cow = jnp.full((b,), mgr.num_pages, jnp.int32)
        feedback = jnp.zeros((budget,), jnp.int32)
        prev_toks = jnp.zeros((b,), jnp.int32)
        emit = jnp.asarray([1, 1], jnp.int32)
        produced = jnp.zeros((b,), jnp.int32)
        keys = jnp.zeros((b, 2), jnp.uint32)
        temp = jnp.asarray([0.0, 0.8], jnp.float32)
        top_k = jnp.asarray([0, 40], jnp.int32)
        top_p = jnp.asarray([1.0, 0.9], jnp.float32)
        pools = ((mgr.k_pages, mgr.v_pages, mgr.k_scales, mgr.v_scales)
                 if mgr.quantize_kv else (mgr.k_pages, mgr.v_pages))
        return (params, tok_ids, tok_slot, tok_pos, q_lens, kv_lens,
                last_idx, spec_len, feedback, prev_toks, emit,
                produced) + pools + (
                    mgr.page_table_device(), no_cow, no_cow, keys, temp,
                    top_k, top_p)

    # fp speculative step: pools donate at the spec-shifted (12, 13)
    mgr = KVCacheManager(cfg.num_layers, cfg.num_heads, cfg.head_dim,
                         num_pages=2 * b * (cfg.max_seq_len // page_size),
                         max_batch=b, max_seq_len=cfg.max_seq_len,
                         page_size=page_size, dtype=jnp.float32,
                         enable_prefix_cache=True)
    step = build_unified_step(cfg, page_size, chunk, spec_k=spec_k)
    args = spec_args(fp_params, mgr)
    closed = trace_callable(step, *args)
    findings += analyze_jaxpr(closed, "serving-spec-step")
    findings += check_donation(step, args, (12, 13), "serving-spec-step")
    # round 23: the spec step rides the per-op activation accounting
    findings += cost_certify("serving-spec-step", closed,
                             params=fp_params, cache=mgr)

    # int8-weight + int8-KV speculative step: pools AND scale planes
    # donate at (12, 13, 14, 15)
    qmgr = KVCacheManager(cfg.num_layers, cfg.num_heads, cfg.head_dim,
                          num_pages=2 * b * (cfg.max_seq_len // page_size),
                          max_batch=b, max_seq_len=cfg.max_seq_len,
                          page_size=page_size, dtype=jnp.float32,
                          quantize_kv=True, enable_prefix_cache=True)
    qstep = build_unified_step(cfg, page_size, chunk, kv_quant=True,
                               spec_k=spec_k)
    qargs = spec_args(q_params, qmgr)
    qclosed = trace_callable(qstep, *qargs)
    findings += analyze_jaxpr(qclosed, "serving-spec-quant-step")
    findings += check_donation(qstep, qargs, (12, 13, 14, 15),
                               "serving-spec-quant-step")
    findings += cost_certify("serving-spec-quant-step", qclosed,
                             params=q_params, cache=qmgr)
    return findings


def analyze_serving_async() -> list[Finding]:
    """Round-13 async serving: the unified step with the device-resident
    feedback path LIVE — a decode lane reading its input token from the
    previous step's ``prev_toks`` carry through the ``feedback`` mask,
    and a sampling lane folding its keys on-device from (base key,
    produced). Jaxpr walk + the JX005 donation audit of the pools at
    their feedback-shifted positions: the async engine threads the pools
    through back-to-back in-flight steps, so a lost donation would
    double-buffer the largest serving allocation."""
    import numpy as np

    import jax.numpy as jnp

    import paddle_tpu as paddle
    from ..inference.kv_cache import KVCacheManager
    from ..models.gpt import (GPTConfig, GPTForCausalLM, build_unified_step,
                              serving_params)

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    params = serving_params(model)
    page_size, chunk, b = 8, 4, 2
    budget = b + chunk
    mgr = KVCacheManager(cfg.num_layers, cfg.num_heads, cfg.head_dim,
                         num_pages=2 * b * (cfg.max_seq_len // page_size),
                         max_batch=b, max_seq_len=cfg.max_seq_len,
                         page_size=page_size, dtype=jnp.float32,
                         enable_prefix_cache=True)
    rng = np.random.RandomState(0)
    for _ in range(b):
        mgr.admit_prefix([int(x) for x in rng.randint(0, 128, (8,))])
    # the steady async shape: slot 0 decodes its IN-FLIGHT token (the
    # feedback lane — tok_ids carries a placeholder the step overrides
    # with prev_toks[0]), slot 1 samples a completing decode token
    tok_ids = jnp.asarray(rng.randint(0, 128, (budget,)), jnp.int32)
    tok_slot = jnp.asarray([0, 1] + [-1] * (budget - 2), jnp.int32)
    tok_pos = jnp.asarray([8, 8] + [0] * (budget - 2), jnp.int32)
    q_lens = jnp.asarray([1, 1], jnp.int32)
    kv_lens = jnp.asarray([8, 8], jnp.int32)
    last_idx = jnp.asarray([0, 1], jnp.int32)
    feedback = jnp.asarray([1, 0] + [0] * (budget - 2), jnp.int32)
    prev_toks = jnp.asarray(rng.randint(0, 128, (b,)), jnp.int32)
    emit = jnp.ones((b,), jnp.int32)
    produced = jnp.asarray([3, 5], jnp.int32)
    no_cow = jnp.full((b,), mgr.num_pages, jnp.int32)
    keys = jnp.asarray(rng.randint(0, 2**31, (b, 2)), jnp.uint32)
    temp = jnp.asarray([0.0, 0.8], jnp.float32)
    top_k = jnp.asarray([0, 40], jnp.int32)
    top_p = jnp.asarray([1.0, 0.9], jnp.float32)

    step = build_unified_step(cfg, page_size, chunk)
    args = (params, tok_ids, tok_slot, tok_pos, q_lens, kv_lens, last_idx,
            feedback, prev_toks, emit, produced,
            mgr.k_pages, mgr.v_pages, mgr.page_table_device(), no_cow,
            no_cow, keys, temp, top_k, top_p)
    closed = trace_callable(step, *args)
    findings = analyze_jaxpr(closed, "serving-async-step")
    findings += check_donation(step, args, (11, 12), "serving-async-step")
    # round 23: the async step is geometry-identical to the unified step;
    # its hbm certification keeps the feedback path inside the model
    findings += cost_certify("serving-async-step", closed, params=params,
                             cache=mgr)
    return findings


def analyze_serving_spec_model() -> list[Finding]:
    """Round-19 model-draft speculative serving: (1) the truncated-layer
    self-draft jit — the first ``draft_layers`` scan stacks of the SAME
    serving params behind the shared embeddings/LM head, built at its
    chunk-1 decode-chain geometry where the feedback carry threads the
    autoregressive draft tokens device-side — and (2) the speculative
    unified step AS THE ASYNC ENGINE DISPATCHES IT behind-by-one: a
    verify lane whose base token rides the ``prev_toks`` carry (feedback
    live on its first verify row). JX005 audits the pool donation of
    both programs — the draft pool threads through every catch-up/chain
    launch exactly like the main pools thread through in-flight steps."""
    import numpy as np

    import jax.numpy as jnp

    import paddle_tpu as paddle
    from ..inference.kv_cache import KVCacheManager
    from ..models.gpt import (GPTConfig, GPTForCausalLM, build_draft_step,
                              build_unified_step, draft_serving_params,
                              serving_params)

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32, spec_draft_layers=1)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    params = serving_params(model)
    rng = np.random.RandomState(0)
    findings: list[Finding] = []

    # (1) the draft chain jit: truncated stack, chunk-1 geometry, one
    # packed row per lane — row 0 feeds a live token, row 1 chains
    # through the feedback carry (the autoregressive draft shape)
    b = 2
    d_params = draft_serving_params(params, 1)
    dmgr = KVCacheManager(1, cfg.num_heads, cfg.head_dim,
                          num_pages=2 * b * (cfg.max_seq_len // 8),
                          max_batch=b, max_seq_len=cfg.max_seq_len,
                          page_size=8, dtype=jnp.float32)
    for _ in range(b):
        dmgr.admit_prefix([int(x) for x in rng.randint(0, 128, (8,))])
    dstep = build_draft_step(cfg, 1, 8, 1)
    no_cow = jnp.full((b,), dmgr.num_pages, jnp.int32)
    dargs = (d_params,
             jnp.asarray(rng.randint(0, 128, (b,)), jnp.int32),
             jnp.arange(b, dtype=jnp.int32),          # tok_slot
             jnp.full((b,), 8, jnp.int32),            # tok_pos
             jnp.ones((b,), jnp.int32),               # q_lens
             jnp.full((b,), 8, jnp.int32),            # kv_lens
             jnp.arange(b, dtype=jnp.int32),          # last_idx
             jnp.asarray([0, 1], jnp.int32),          # feedback: row 1 chains
             jnp.asarray(rng.randint(0, 128, (b,)), jnp.int32),
             jnp.ones((b,), jnp.int32),               # emit_mask
             jnp.zeros((b,), jnp.int32),              # produced
             dmgr.k_pages, dmgr.v_pages, dmgr.page_table_device(),
             no_cow, no_cow, jnp.zeros((b, 2), jnp.uint32),
             jnp.zeros((b,), jnp.float32), jnp.zeros((b,), jnp.int32),
             jnp.ones((b,), jnp.float32))
    findings += analyze_jaxpr(trace_callable(dstep, *dargs),
                              "serving-spec-model-draft-step")
    findings += check_donation(dstep, dargs, (11, 12),
                               "serving-spec-model-draft-step")

    # (2) the spec step as the async engine dispatches it behind-by-one:
    # slot 0 verifies 1 + 2 drafts with its BASE token still in flight
    # (feedback live on the first verify row), slot 1 a draftless spec
    # lane riding the carry too
    page_size, chunk, spec_k = 8, 8, 3
    budget = b * (1 + spec_k) + chunk
    mgr = KVCacheManager(cfg.num_layers, cfg.num_heads, cfg.head_dim,
                         num_pages=2 * b * (cfg.max_seq_len // page_size),
                         max_batch=b, max_seq_len=cfg.max_seq_len,
                         page_size=page_size, dtype=jnp.float32,
                         enable_prefix_cache=True)
    for _ in range(b):
        mgr.admit_prefix([int(x) for x in rng.randint(0, 128, (8,))])
    tok_ids = jnp.asarray(rng.randint(0, 128, (budget,)), jnp.int32)
    tok_slot = jnp.asarray([0] * 3 + [1] + [-1] * (budget - 4), jnp.int32)
    tok_pos = jnp.asarray(list(range(8, 11)) + [8] + [0] * (budget - 4),
                          jnp.int32)
    q_lens = jnp.asarray([3, 1], jnp.int32)
    kv_lens = jnp.asarray([8, 8], jnp.int32)
    last_idx = jnp.asarray([0, 3], jnp.int32)
    spec_len = jnp.asarray([2, 0], jnp.int32)
    feedback = jnp.asarray([1, 0, 0, 1] + [0] * (budget - 4), jnp.int32)
    prev_toks = jnp.asarray(rng.randint(0, 128, (b,)), jnp.int32)
    no_cow2 = jnp.full((b,), mgr.num_pages, jnp.int32)
    step = build_unified_step(cfg, page_size, chunk, spec_k=spec_k)
    args = (params, tok_ids, tok_slot, tok_pos, q_lens, kv_lens, last_idx,
            spec_len, feedback, prev_toks, jnp.ones((b,), jnp.int32),
            jnp.asarray([3, 5], jnp.int32),
            mgr.k_pages, mgr.v_pages, mgr.page_table_device(), no_cow2,
            no_cow2, jnp.asarray(rng.randint(0, 2**31, (b, 2)),
                                 jnp.uint32),
            jnp.asarray([0.0, 0.8], jnp.float32),
            jnp.asarray([0, 40], jnp.int32),
            jnp.asarray([1.0, 0.9], jnp.float32))
    findings += analyze_jaxpr(trace_callable(step, *args),
                              "serving-spec-model-async-step")
    findings += check_donation(step, args, (12, 13),
                               "serving-spec-model-async-step")
    return findings


def analyze_serving_tiered() -> list[Finding]:
    """Round 21: the tiered KV cache's batched restore landing —
    :func:`paddle_tpu.inference.kv_cache.batched_import_rows`, the one
    jitted scatter a host-tier restore round (or a batched transfer
    tick) issues per (K, V, scale) plane. The jaxpr walk covers every
    plane geometry the landing zone drives it with — the 5D fp pool,
    the 5D int8 pool, and the 4D fp32 scale plane — at a
    power-of-two-padded row width (the pad rows route to the
    ``num_pages`` out-of-bounds sentinel and drop, so the trace is the
    production trace); JX005 audits the pool donation at argument 0."""
    import numpy as np

    import jax.numpy as jnp

    from ..inference.kv_cache import KVCacheManager, batched_import_rows

    mgr = KVCacheManager(2, 2, 8, num_pages=8, max_batch=2,
                         max_seq_len=32, page_size=8, dtype=jnp.float32,
                         enable_prefix_cache=True)
    qmgr = KVCacheManager(2, 2, 8, num_pages=8, max_batch=2,
                          max_seq_len=32, page_size=8,
                          enable_prefix_cache=True, quantize_kv=True)
    cap = 16                                 # one padded restore round
    rng = np.random.RandomState(0)
    pg = jnp.asarray(rng.randint(0, 8, (cap,)), jnp.int32)
    row = jnp.asarray(np.tile(np.arange(8, dtype=np.int32), 2))
    findings: list[Finding] = []
    for target, pool, vals in (
            ("serving-tiered-restore-fp", mgr.k_pages,
             jnp.zeros((2, cap, 2, 8), mgr.k_pages.dtype)),
            ("serving-tiered-restore-int8", qmgr.k_pages,
             jnp.zeros((2, cap, 2, 8), qmgr.k_pages.dtype)),
            ("serving-tiered-restore-scale", qmgr.k_scales,
             jnp.zeros((2, cap, 2), qmgr.k_scales.dtype))):
        args = (pool, vals, pg, row)
        closed = trace_callable(batched_import_rows, *args)
        findings += analyze_jaxpr(closed, target)
        findings += check_donation(batched_import_rows, args, (0,),
                                   target)
        # round 23: a restore landing is a pure local scatter — its
        # committed collective inventory is EMPTY
        findings += cost_certify(target, closed)
    return findings


def analyze_serving_moe() -> list[Finding]:
    """Round 25: the MoE unified step — the same mixed prefill+decode
    geometry as ``serving-unified`` but with the routed-expert FFN
    (``moe_experts=4, moe_top_k=2``) replacing the dense MLP. The jaxpr
    walk covers the top-k routing, the capacity sort and the grouped
    combine; JX005 audits the page-pool donation at the SAME positions
    (the MoE swap must not reorder the step's arguments); cost_certify
    gates the JX007 hbm model's routed-weight accounting (a token
    streams top_k/E of the expert bytes) and the EMPTY collective
    inventory (experts replicate under mp on the per-op path)."""
    import numpy as np

    import jax.numpy as jnp

    import paddle_tpu as paddle
    from ..inference.kv_cache import KVCacheManager
    from ..models.gpt import (GPTConfig, GPTForCausalLM, build_unified_step,
                              serving_params)

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32, moe_experts=4,
                    moe_top_k=2)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    params = serving_params(model)
    page_size, chunk, b = 8, 4, 2
    budget = b + chunk
    mgr = KVCacheManager(cfg.num_layers, cfg.num_heads, cfg.head_dim,
                         num_pages=2 * b * (cfg.max_seq_len // page_size),
                         max_batch=b, max_seq_len=cfg.max_seq_len,
                         page_size=page_size, dtype=jnp.float32)
    rng = np.random.RandomState(0)
    tok_ids = jnp.asarray(rng.randint(0, 128, (budget,)), jnp.int32)
    tok_slot = jnp.asarray([0] + [1] * chunk + [-1] * (budget - 1 - chunk),
                           jnp.int32)
    tok_pos = jnp.asarray([0] + list(range(chunk))
                          + [0] * (budget - 1 - chunk), jnp.int32)
    q_lens = jnp.asarray([1, chunk], jnp.int32)
    kv_lens = mgr.seq_lens_device() * 0
    last_idx = jnp.asarray([0, chunk], jnp.int32)
    no_cow = jnp.full((b,), mgr.num_pages, jnp.int32)
    feedback = jnp.zeros((budget,), jnp.int32)
    prev_toks = jnp.zeros((b,), jnp.int32)
    emit = jnp.asarray([1, 0], jnp.int32)
    produced = jnp.zeros((b,), jnp.int32)
    keys = jnp.zeros((b, 2), jnp.uint32)
    temp = jnp.asarray([0.0, 0.8], jnp.float32)
    top_k = jnp.asarray([0, 40], jnp.int32)
    top_p = jnp.asarray([1.0, 0.9], jnp.float32)

    step = build_unified_step(cfg, page_size, chunk)
    args = (params, tok_ids, tok_slot, tok_pos, q_lens, kv_lens, last_idx,
            feedback, prev_toks, emit, produced,
            mgr.k_pages, mgr.v_pages, mgr.page_table_device(), no_cow,
            no_cow, keys, temp, top_k, top_p)
    findings = analyze_jaxpr(trace_callable(step, *args),
                             "serving-moe-step")
    findings += check_donation(step, args, (11, 12), "serving-moe-step")
    kstep = build_unified_step(cfg, page_size, chunk, use_kernel=True)
    findings += cost_certify("serving-moe-step",
                             trace_callable(kstep, *args), params=params,
                             cache=mgr)
    return findings


def analyze_train_moe_ep() -> list[Finding]:
    """Round 25: the expert-parallel MoE train step —
    ``build_spmd_train_step`` over the 4-axis (dp, pp, mp, ep=2) mesh
    with the expert stacks sharded on "ep" and the per-ep-group combine
    riding the int8 quantized ring (``quantized_all_reduce_stacked``).
    The jaxpr walk covers the einsum dispatch, the ep-sharded expert
    FFN and the quantize/roll/dequant combine hops; JX005 audits the
    (params, momentum) donation; the HLO certification compiles the
    step and checks the wire — s8 payloads present (the ep combine's
    collective-permutes), fp all-reduces bounded to the small mp
    activation psums (the widened allowance in the contract row)."""
    import jax

    from ..distributed.mesh import make_training_mesh
    from ..models.gpt import GPTConfig
    from ..models.gpt_spmd import build_spmd_train_step

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32, moe_experts=4,
                    moe_top_k=2)
    if len(jax.devices()) < 2:
        # mirrors train-dpquant: ep=1 would trace the collective-free
        # einsum path and certify a false-green empty wire
        raise RuntimeError(
            "train-moe-ep needs >= 2 devices (the ep combine is inert "
            "at ep=1); run under the forced virtual CPU mesh like the "
            "`python -m paddle_tpu.analysis` gate")
    mesh = make_training_mesh(min(len(jax.devices()), 8), ep=2)
    step, params, mom, (ids, labels) = build_spmd_train_step(
        cfg, mesh, batch_size=4, seq_len=32, comm_quant="int8")
    closed = trace_callable(step, params, mom, ids, labels)
    findings = analyze_jaxpr(closed, "train-moe-ep-step")
    findings += check_donation(step, (params, mom, ids, labels), (0, 1),
                               "train-moe-ep-step")
    findings += hlo_certify("train-moe-ep-step", step,
                            (params, mom, ids, labels), mesh=mesh)
    return findings


TARGETS = {
    "gpt-eager": analyze_gpt_eager,
    "bert-eager": analyze_bert_eager,
    "gpt-spmd": analyze_gpt_spmd,
    "train-dpquant": analyze_train_dpquant,
    "serving-unified": analyze_serving_unified,
    "serving-quant": analyze_serving_quant,
    "serving-spmd": analyze_serving_spmd,
    "serving-spec": analyze_serving_spec,
    "serving-spec-model": analyze_serving_spec_model,
    "serving-async": analyze_serving_async,
    "serving-tiered": analyze_serving_tiered,
    "serving-moe": analyze_serving_moe,
    "train-moe-ep": analyze_train_moe_ep,
}


def analyze_flagships(names=None) -> list[Finding]:
    out: list[Finding] = []
    for name, fn in TARGETS.items():
        if names is not None and name not in names:
            continue
        out.extend(fn())
    return out
