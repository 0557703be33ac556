"""CLI: the L9 surface (SURVEY.md 2.1).

Command tree parity with the reference (`polyaxon run/ops/config/version`
et al.), TPU-first semantics: local mode executes in-process against the
file store; API mode (POLYAXON_TPU_HOST) goes through the control plane.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

import click

from polyaxon_tpu import __version__


def _parse_params(params: Tuple[str, ...]) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for item in params:
        if "=" not in item:
            raise click.BadParameter(
                f"-P expects name=value, got {item!r}")
        key, _, value = item.partition("=")
        out[key.strip()] = value
    return out


def _echo_record(record: Dict[str, Any], fields: Optional[List[str]] = None):
    fields = fields or ["uuid", "name", "kind", "status", "created_at",
                        "duration"]
    for f in fields:
        click.echo(f"{f:>12}: {record.get(f)}")


@click.group(name="ptpu")
@click.version_option(version=__version__, prog_name="polyaxon-tpu")
def cli():
    """polyaxon-tpu: TPU-native ML orchestration.

    Declarative specs -> compile -> run (local or TPU slices) -> track ->
    tune -> stream.
    """


# ---------------------------------------------------------------------------
# run
# ---------------------------------------------------------------------------


@cli.command()
@click.option("-f", "--file", "files", multiple=True, required=True,
              type=click.Path(), help="Polyaxonfile(s) to run (merged in order).")
@click.option("-P", "--param", "params", multiple=True,
              help="Param override: -P lr=0.1 (repeatable).")
@click.option("--preset", "presets", multiple=True, type=click.Path(),
              help="Preset file(s) applied before -P params.")
@click.option("--name", default=None, help="Run name override.")
@click.option("--project", default="default", help="Project name.")
@click.option("--watch/--no-watch", default=True,
              help="Stream logs while running (local mode).")
@click.option("--eager", is_flag=True, default=False,
              help="Force local in-process execution even in API mode.")
@click.option("--check-only", is_flag=True, default=False,
              help="Validate and print the operation without running.")
@click.option("--queue", default=None,
              help="Queue override (API mode; else from the spec).")
@click.option("--priority", default=None, type=int,
              help="Priority override, higher claims first (API mode).")
def run(files, params, presets, name, project, watch, eager, check_only,
        queue, priority):
    """Run a polyaxonfile: compile, execute, track."""
    from polyaxon_tpu.polyaxonfile import check_polyaxonfile
    from polyaxon_tpu.polyaxonfile.reader import PolyaxonfileError

    try:
        op = check_polyaxonfile(list(files), params=_parse_params(params),
                                presets=list(presets) or None)
    except (PolyaxonfileError, ValueError) as e:
        raise click.ClickException(f"Invalid polyaxonfile: {e}")

    if check_only:
        click.echo(json.dumps(op.to_dict(), indent=2, default=str))
        return

    host = os.environ.get("POLYAXON_TPU_HOST")
    if host and not eager:
        from polyaxon_tpu.client import RunClient

        client = RunClient(project=project)
        record = client.create(name=name or op.name, content=op.to_dict(),
                               kind=getattr(op.component.run, "kind", None)
                               if op.has_component else None,
                               managed_by="agent",
                               queue=queue or op.effective_queue,
                               priority=priority if priority is not None
                               else op.effective_priority)
        client.log_status("queued", reason="CliSubmit", force=True)
        click.echo(f"Run {record['uuid']} queued on {host}")
        return

    from polyaxon_tpu.runner import LocalExecutor

    if queue or priority is not None:
        click.echo("note: --queue/--priority apply to queued (API-mode) "
                   "submission; this local run executes immediately.",
                   err=True)
    if name:
        op = op.model_copy(update={"name": name})
    executor = LocalExecutor(project=project, stream_logs=watch)
    try:
        record = executor.run_operation(op)
    except Exception as e:
        raise click.ClickException(f"Run failed: {e}")
    status = record.get("status")
    _echo_record(record)
    if status == "running" and record.get("kind") == "service":
        # RUNNING is the service's steady state, not a failure: it
        # stays up detached until `ops stop` reaps it.
        svc = (record.get("meta_info") or {}).get("service") or {}
        ports = svc.get("ports") or []
        where = f" on port {ports[0]}" if ports else ""
        click.echo(f"service is up{where}; stop with "
                   f"`ptpu ops stop {record['uuid']}`")
        return
    if status != "succeeded":
        logs = executor.store.read_logs(record["uuid"], tail=20)
        if logs:
            click.echo("--- last logs ---")
            click.echo(logs)
        raise click.ClickException(f"Run finished with status {status!r}")


# ---------------------------------------------------------------------------
# generate (serving)
# ---------------------------------------------------------------------------


def _parse_prompt(prompt: str):
    """``"1,2,3"`` -> one row; ``@file.json`` -> list of rows (all the
    same length — ragged prompts must be padded upstream)."""
    import json as _json

    if prompt.startswith("@"):
        try:
            with open(prompt[1:]) as f:
                rows = _json.load(f)
        except (OSError, ValueError) as e:
            raise click.ClickException(
                f"cannot read prompt file {prompt[1:]!r}: {e}")
        if not isinstance(rows, list):
            raise click.ClickException(
                "prompt file must hold a JSON list of token ids or a "
                "list of rows")
        if not rows or not isinstance(rows[0], list):
            rows = [rows]
    else:
        rows = [[t for t in prompt.split(",") if t.strip()]]
    try:
        rows = [[int(t) for t in r] for r in rows]
    except (TypeError, ValueError) as e:
        raise click.ClickException(
            f"prompt rows must contain integer token ids: {e}")
    if not rows or not rows[0]:
        raise click.ClickException("prompt must contain at least one "
                                   "token id")
    if len({len(r) for r in rows}) != 1:
        raise click.ClickException(
            "All prompt rows must share one length (pad upstream)")
    return rows


def _build_serving_model(name: str, batch_size: int,
                         ckpt_dir, kv_int8: bool, int8_weights: bool,
                         kv_ring: bool = False, kv_ring_slack: int = 0):
    """Shared by ``generate`` and ``serve``: zoo model + variables
    with the serving options applied (int8 KV / ring-cache config,
    checkpoint restore, weight quantization, and the tree at rest in
    the dtype the modules compute in: serving/weights.py), and the
    float32 bytes that last step rounded.  A draft model's tree goes
    the same way: both commands build it through this function."""
    from polyaxon_tpu.models.registry import get_model
    from polyaxon_tpu.serving.weights import (declared_tree,
                                              rest_as_declared,
                                              resting_overrides)

    spec = get_model(name)
    kw = {}
    if kv_int8:
        kw["kv_cache_int8"] = True
    if kv_ring:
        kw["kv_cache_ring"] = True
        if kv_ring_slack:
            # speculative decoding on a ring cache needs spare slots
            # for rollback overwrites (generate_speculative's guard)
            kw["kv_cache_ring_slack"] = int(kv_ring_slack)
    try:
        if ckpt_dir:
            # Restoring replaces the params — don't pay a full random
            # init just to discard it.
            model = spec.make_model(**kw)
            variables = None
        else:
            model, variables = spec.init_params(
                batch_size=batch_size, **kw)
    except TypeError:
        if kw:
            # Name only the fields the family actually lacks: a
            # combined --int8-kv --kv-ring on gpt2 fails on kv_ring
            # alone, and blaming both would point the user at the
            # wrong flag.
            import dataclasses as _dc

            cfg = getattr(spec.make_model(), "cfg", None)
            known = ({f.name for f in _dc.fields(cfg)}
                     if _dc.is_dataclass(cfg) else set())
            bad = sorted(k for k in kw if k not in known) or sorted(kw)
            raise click.ClickException(
                f"{name} does not support {bad} (no such config "
                f"field{'s' if len(bad) > 1 else ''} on this model "
                f"family)")
        # No config kwarg was passed, so the TypeError is a real bug
        # inside model construction — masking it as a quantization
        # message would point the user at the wrong flag.
        raise
    except ValueError as e:
        if kw:
            # Config-level validation of a passed flag (e.g.
            # kv_cache_ring on a model without sliding_window) — a
            # clean CLI error, not a traceback.
            raise click.ClickException(str(e))
        # No serving flag was passed: a real library bug, keep the
        # stack (same contract as the TypeError branch above).
        raise
    if ckpt_dir:
        from polyaxon_tpu.checkpoint import CheckpointManager

        state = CheckpointManager(directory=ckpt_dir).restore()
        restored = state.get("params") if isinstance(state, dict) \
            else None
        if restored is None:
            raise click.ClickException(
                f"checkpoint under {ckpt_dir} has no 'params'")
        # Train state stores the full flax variables dict under
        # "params" (TrainStep.init_state) — don't re-wrap it.
        variables = restored if isinstance(restored, dict) \
            and "params" in restored else {"params": restored}
    if int8_weights:
        from polyaxon_tpu.ops.quant import quantize_params

        variables = {"params": quantize_params(variables["params"])}
    # Made, restored and quantized in float32 as ever; THEN each leaf
    # is rounded once to what the serving model declares for it (the
    # float32 buffers go with the old tree).
    cast_bytes = 0
    rest = resting_overrides(model)
    if rest:
        model = spec.make_model(**kw, **rest)
        variables, cast_bytes = rest_as_declared(
            variables, declared_tree(
                model, spec.make_batch(batch_size)["inputs"]))
    return model, variables, cast_bytes


@cli.command()
@click.option("--model", "model_name", required=True,
              help="Zoo model name (see models/registry.py).")
@click.option("--prompt", required=True,
              help="Comma-separated token ids, or @file.json with a "
                   "list of rows.")
@click.option("--max-new-tokens", default=32, type=int)
@click.option("--temperature", default=0.0, type=float,
              help="0 = greedy.")
@click.option("--top-k", default=None, type=int)
@click.option("--top-p", default=None, type=float,
              help="Nucleus sampling mass.")
@click.option("--beams", default=1, type=int,
              help=">1 switches to beam search (greedy scoring).")
@click.option("--eos-id", default=None, type=int)
@click.option("--checkpoint", default=None, type=click.Path(),
              help="Orbax checkpoint dir from `ptpu train` "
                   "(--checkpoint-every); default: random init.")
@click.option("--draft-model", "--spec-draft", "draft_model",
              default=None,
              help="Zoo model for SPECULATIVE decoding (same vocab; "
                   "--spec-draft is an alias). Greedy by default "
                   "(output identical to the target's greedy "
                   "decode); with --temperature it runs rejection "
                   "speculative sampling — exact target-distribution "
                   "samples for any draft, under the position-keyed "
                   "--seed schedule the server's engine uses.")
@click.option("--draft-checkpoint", default=None, type=click.Path())
@click.option("--spec-k", default=4, type=int,
              help="Draft proposals per speculative round.")
@click.option("--int8-weights", is_flag=True, default=False,
              help="Weight-only int8 (halves weight HBM reads).")
@click.option("--int8-kv", is_flag=True, default=False,
              help="int8 KV cache (halves KV HBM reads).")
@click.option("--kv-ring", is_flag=True, default=False,
              help="O(window) ring KV cache for sliding-window "
                   "models: stream past max_position (composes with "
                   "beam and --int8-kv).")
@click.option("--seed", default=0, type=int)
@click.option("--prefill-chunk", default=None, type=int,
              help="Prefill the prompt in fixed-size pieces to bound "
                   "activation memory (long prompts).")
@click.option("--cpu", is_flag=True, default=False)
def generate(model_name, prompt, max_new_tokens, temperature, top_k,
             top_p, beams, eos_id, checkpoint, draft_model,
             draft_checkpoint, spec_k, int8_weights, int8_kv,
             kv_ring, seed, prefill_chunk, cpu):
    """Decode with a zoo model — the native serving surface.

    The reference serves models as opaque user containers behind
    `V1Service`; here the framework owns the decode loop (compile-once
    scan, chunked prefill, KV cache), so sampling, beam search,
    speculative decoding and int8 serving are first-class flags.
    Emits one JSON object: tokens plus timing.
    """
    import json as _json
    import time as _time

    import jax

    if cpu:
        jax.config.update("jax_platforms", "cpu")
    from polyaxon_tpu.config import enable_compilation_cache

    enable_compilation_cache()
    from polyaxon_tpu.models import generate as G
    from polyaxon_tpu.models.registry import get_model

    rows = _parse_prompt(prompt)
    b = len(rows)

    # Speculative rounds on a ring cache overwrite up to k-1 still-
    # in-window slots on rollback: build both models with that slack
    # so --kv-ring + --draft-model works out of the box.
    ring_slack = (spec_k - 1) if (kv_ring and draft_model) else 0
    model, variables, _ = _build_serving_model(
        model_name, b, checkpoint, int8_kv, int8_weights,
        kv_ring=kv_ring, kv_ring_slack=ring_slack)
    import numpy as np

    toks = np.asarray(rows, dtype=np.int32)
    t0 = _time.perf_counter()
    try:
        # Uniform sampling-param validation (same messages as the
        # server): an explicit --top-k 0 / --top-p 0 must be refused
        # on every decode path, not silently treated as "disabled" by
        # the positional branch's internal 0-encoding.
        G._check_top_k(top_k, getattr(getattr(model, "cfg", None),
                                      "vocab_size", None))
        G._check_top_p(top_p)
        if draft_model is not None:
            # Shared validation (ONE message with the server and the
            # library): spec_k >= 1, no speculative+beam.
            G._check_spec_k(spec_k)
            if beams > 1:
                raise click.ClickException(G.SPEC_BEAM_MSG)
            if temperature == 0.0 and (top_k is not None
                                       or top_p is not None):
                raise click.ClickException(
                    "speculative --top-k/--top-p need --temperature "
                    "> 0 (temperature=0 is greedy and would ignore "
                    "them)")
            draft, draft_vars, _ = _build_serving_model(
                draft_model, b, draft_checkpoint, int8_kv,
                int8_weights, kv_ring=kv_ring,
                kv_ring_slack=ring_slack)
            # temperature>0 runs rejection speculative sampling under
            # the POSITION-KEYED --seed schedule (exact target-
            # distribution samples for any draft) — the same schedule
            # the server's engine and solo paths run, so `ptpu
            # generate --seed N` matches a served request with seed N.
            out = G.generate_speculative(
                model, variables, draft, draft_vars, toks,
                max_new_tokens=max_new_tokens, k=spec_k, eos_id=eos_id,
                prefill_chunk=prefill_chunk, temperature=temperature,
                top_k=top_k, top_p=top_p,
                seed=seed if temperature != 0.0 else None)
        elif beams > 1:
            if temperature != 0.0 or top_k is not None \
                    or top_p is not None:
                raise click.ClickException(
                    "beam search is deterministic (no --temperature, "
                    "--top-k or --top-p)")
            out = G.generate_beam(model, variables, toks,
                                  max_new_tokens=max_new_tokens,
                                  num_beams=beams, eos_id=eos_id,
                                  prefill_chunk=prefill_chunk)
        elif G.positional_eligible(model, temperature):
            # Decoder-only sampled decode uses the POSITION-KEYED
            # schedule (token i's key is a function of --seed, row,
            # and i alone), the same contract the server's
            # continuous-batching engine samples under — so `ptpu
            # generate --seed N` and a served request with seed N
            # return the same tokens.
            out = G.generate_positional(model, variables, toks,
                                        max_new_tokens=max_new_tokens,
                                        temperature=temperature,
                                        top_k=top_k, top_p=top_p,
                                        eos_id=eos_id, seed=seed,
                                        prefill_chunk=prefill_chunk)
        else:
            out = G.generate(model, variables, toks,
                             max_new_tokens=max_new_tokens,
                             temperature=temperature, top_k=top_k,
                             top_p=top_p, eos_id=eos_id,
                             rng=jax.random.PRNGKey(seed),
                             prefill_chunk=prefill_chunk)
    except (ValueError, NotImplementedError) as e:
        # Library-level validation (max_position overflow, top_p
        # range, unsupported mode combinations like beam on unstacked
        # layers) — surface as a clean CLI error, not a traceback.
        raise click.ClickException(str(e))
    out = np.asarray(jax.device_get(out))
    dt = _time.perf_counter() - t0
    p_len = toks.shape[1]
    click.echo(_json.dumps({
        "model": model_name,
        "tokens": out.tolist(),
        "new_tokens": out[:, p_len:].tolist(),
        "wall_s": round(dt, 3),
        "tok_per_sec": round(b * max_new_tokens / dt, 1),
        "backend": jax.default_backend(),
        **({"draft_model": draft_model, "spec_k": spec_k}
           if draft_model else {}),
        **({"int8_weights": True} if int8_weights else {}),
        **({"int8_kv": True} if int8_kv else {}),
        **({"kv_ring": True} if kv_ring else {}),
    }))


@cli.command()
@click.option("--model", "model_name", required=True)
@click.option("--host", default="127.0.0.1")
@click.option("--port", default=8000, type=int)
@click.option("--checkpoint", default=None, type=click.Path())
@click.option("--int8-weights", is_flag=True, default=False)
@click.option("--int8-kv", is_flag=True, default=False)
@click.option("--kv-ring", is_flag=True, default=False,
              help="O(window) ring KV cache (sliding-window models).")
@click.option("--kv-ring-slack", default=0, type=int,
              help="Spare ring slots beyond the window; speculative "
                   "requests need >= spec_k - 1 (default 0 rejects "
                   "them).")
@click.option("--prefix-cache", default=4, type=int,
              help="Prefix-cache entries (POST /prefill registers a "
                   "system prompt; matching /generate requests skip "
                   "its prefill). 0 disables; each entry holds a full "
                   "KV cache on device.")
@click.option("--max-batch", default=8, type=int)
@click.option("--batching", default="continuous",
              type=click.Choice(["continuous", "coalesce", "off"]),
              help="Batching policy: continuous (slot-based engine "
                   "serving greedy AND sampled requests, default), "
                   "coalesce (legacy whole-request merging of greedy "
                   "traffic; sampled decodes solo), off (serialize).")
@click.option("--slots", "n_slots", default=8, type=int,
              help="Continuous-batching decode slots (physical batch "
                   "width; KV memory = slots x one request cache).")
@click.option("--queue-depth", default=64, type=int,
              help="Admission-queue bound (rows); a full queue "
                   "returns 429 + Retry-After.")
@click.option("--prefill-chunk", default=None, type=int,
              help="Default interleaved-prefill chunk (tokens); long "
                   "prompts prefill one chunk per decode boundary.")
@click.option("--decode-window", default=8, type=int,
              help="Max decode steps fused per device dispatch when "
                   "no admission could happen sooner (the engine "
                   "drops to single steps under admission pressure).")
@click.option("--mesh", "mesh_arg", default=None,
              help="Serve over a device mesh, e.g. 'tp=4' or "
                   "'tp=2,ep=2': params go under NamedSharding and "
                   "the slot KV cache shards its heads axis over tp "
                   "(experts over ep; dp shards the slot axis on "
                   "fixed-lane pools).  The exact serving layout — "
                   "meshed responses are token-bitwise-identical to "
                   "unmeshed ones per seed.  Requires --batching "
                   "continuous and dp*tp*ep local devices.")
@click.option("--kv-paged", is_flag=True, default=False,
              help="Paged KV cache: slot KV lives in a pool of "
                   "fixed-size pages with per-slot page tables and "
                   "copy-on-write shared-prefix pages, so occupancy "
                   "is bounded by token usage instead of slots x "
                   "max_position lanes (continuous batching, "
                   "plain/int8 caches only).")
@click.option("--kv-page-tokens", default=64, type=int,
              help="With --kv-paged: positions per KV page "
                   "(>= 8; smaller pages pack tighter, bigger pages "
                   "gather/scatter less).")
@click.option("--kv-pages", default=None, type=int,
              help="With --kv-paged: page-pool size in pages "
                   "(default: the fixed-lane footprint, slots x "
                   "ceil(max_position / page size) — same memory, "
                   "paged layout).")
@click.option("--kv-lazy", is_flag=True, default=False,
              help="With --kv-paged: LAZY page reservation — "
                   "admission reserves prompt + one decode window "
                   "instead of the full budget, slots grow their "
                   "page tables at step boundaries, and pool "
                   "exhaustion preempts the resident with the most "
                   "remaining budget (token-identical resume).  "
                   "Packs more residents when outputs run short of "
                   "budget.")
@click.option("--kv-host-spill-bytes", default=0, type=int,
              help="With --kv-paged: host-RAM byte budget for the "
                   "prefix store's SPILL tier — entries evicted from "
                   "device pages under pressure spill their payloads "
                   "to host buffers instead of dropping; a hit "
                   "re-materializes via device_put (and promotes "
                   "back to pages when the pool has room).  0 "
                   "(default) keeps the drop-on-evict behavior.")
@click.option("--prefix-fetch/--no-prefix-fetch", default=False,
              help="With --kv-paged and --kv-host-spill-bytes: arm "
                   "the FLEET prefix tier's wire-fetch client — a "
                   "local prefix miss carrying a router hint "
                   "({\"prefix_hint\": ...}) fetches the holder's "
                   "spilled payload over HTTP (checksummed; any "
                   "failure degrades to re-prefill, counted in "
                   "prefix_fetch_failed_total).  The SERVING half "
                   "(/prefix/fetch|ingest|handoff|evict, GET "
                   "/prefix/index) is always mounted on paged "
                   "servers.")
@click.option("--prefix-fetch-timeout", default=5.0, type=float,
              help="Per-connection timeout (seconds) for wire "
                   "fetches and handoff pushes.")
@click.option("--prefix-fetch-min-tokens", default=16, type=int,
              help="Fetch-policy floor: prefixes shorter than this "
                   "re-prefill locally (wire RTT beats tiny "
                   "prefills).")
@click.option("--prefix-fetch-remat-ratio", default=0.26, type=float,
              help="Fetch-policy curve: rematerialization cost as a "
                   "fraction of re-prefill cost (the measured "
                   "spilled-hit ratio; docs/SERVING.md).")
@click.option("--role", default="both",
              type=click.Choice(["prefill", "decode", "both"]),
              help="Disaggregated-serving role (docs/SERVING.md "
                   "\"Disaggregated serving\"). 'both' (default) is "
                   "today's monolithic replica, byte-for-byte. "
                   "'prefill' runs prompt prefill only — serves "
                   "/prefill and the /prefix/* wire lanes, rejects "
                   "/generate (400), no decode residents; needs "
                   "--kv-paged and --kv-host-spill-bytes. 'decode' "
                   "pulls handed-off KV over the wire-fetch lane; "
                   "needs --prefix-fetch. The router learns roles "
                   "from /healthz and schedules prefill->decode as "
                   "a two-stage attempt.")
@click.option("--default-priority", default="interactive",
              type=click.Choice(["interactive", "batch"]),
              help="Priority class for requests that don't declare "
                   "one ({\"priority\": ...}): interactive drains "
                   "ahead of batch, and batch decodes are "
                   "preemptible under --slo-ttft-ms.")
@click.option("--batch-queue-depth", default=None, type=int,
              help="Admission-queue bound (rows) for the BATCH "
                   "class (default: --queue-depth; the interactive "
                   "class always uses --queue-depth).")
@click.option("--queue-deadline-ms", default=None, type=int,
              help="Shed an INTERACTIVE request (503 + reason "
                   "queue_deadline) that got zero engine attention "
                   "for this long — it could not start before its "
                   "deadline, so don't let it rot in the queue.")
@click.option("--batch-queue-deadline-ms", default=None, type=int,
              help="Same shed deadline for the BATCH class queue.")
@click.option("--slo-ttft-ms", default=None, type=int,
              help="Interactive TTFT SLO target: when the "
                   "interactive class's admission-anchored TTFT p99 "
                   "(or the waiting head's own age) degrades past "
                   "this, the scheduler preempts the longest batch "
                   "decode and requeues it with its "
                   "generated-so-far prefix (token-identical "
                   "resume). Unset = never preempt.")
@click.option("--request-timeout", default=600.0, type=float,
              help="Bounded front-end wait (seconds) for "
                   "engine-path requests: one with no terminal "
                   "state after this long is shed with 503 + reason "
                   "request_timeout instead of holding its HTTP "
                   "worker until engine drain. Solo/coalesce paths "
                   "bound waits via deadline checks at their "
                   "dispatch boundaries.")
@click.option("--draft-model", "--spec-draft", "draft_model",
              default=None,
              help="Zoo model enabling SPECULATIVE requests "
                   "({\"speculative\": true}); same vocab as --model "
                   "(--spec-draft is an alias). With the default "
                   "--batching continuous, speculative requests ride "
                   "the engine's slot pool.")
@click.option("--draft-checkpoint", default=None, type=click.Path())
@click.option("--spec-k", default=4, type=int,
              help="Default draft proposals per speculative round "
                   "for requests that don't pass spec_k — and the "
                   "engine's cap: requests asking for more decode "
                   "solo.")
@click.option("--trace-buffer", default=4096, type=int,
              help="Telemetry ring capacity in trace events (request "
                   "lifecycle spans + engine step records, exported "
                   "by GET /trace as Chrome trace JSON). 0 disables "
                   "span recording; /metrics histograms stay live.")
@click.option("--trace-file", default=None, type=click.Path(),
              help="Dump the telemetry ring to this JSONL file on "
                   "shutdown (one trace event per line).")
@click.option("--profile-dir", default=None, type=click.Path(),
              help="Enable POST /profile/start|stop: jax.profiler "
                   "device traces land in timestamped subdirs here "
                   "(omit to keep the endpoints disabled).")
@click.option("--profile-every", default=0, type=int,
              help="FLIGHT RECORDER (needs --profile-dir): every N "
                   "decode dispatches, wrap --profile-steps step "
                   "boundaries in a jax.profiler window, auto-analyze "
                   "the dump, and publish trace-true attribution — "
                   "collective/host-gap/device-busy shares + serving "
                   "MFU — as /metrics gauges and GET /profile/report. "
                   "0 (default) disables.")
@click.option("--profile-steps", default=8, type=int,
              help="With --profile-every: decode dispatches per "
                   "recorder window.")
@click.option("--access-log", is_flag=True, default=False,
              help="One structured JSON line per request on stderr "
                   "(status, kind, rows, tokens, latency) — includes "
                   "failed requests, which are otherwise silent.")
@click.option("--sanitize", is_flag=True, default=False,
              help="Wrap the serving locks in the lock-order "
                   "sanitizer (analysis/locksan.py): raises on "
                   "lock-order inversion, reports in /info. Debug "
                   "aid — off by default (and off in benchmark "
                   "runs; see bench_serving_load.py --sanitize).")
@click.option("--sanitize-max-hold", default=None, type=float,
              help="With --sanitize: flag device_lock holds longer "
                   "than this many seconds (unset = no hold limit).")
@click.option("--sanitize-report", "sanitize_report", default=None,
              type=click.Path(),
              help="With --sanitize: write the observed lock "
                   "acquisition graph (the same dict /info reports) "
                   "to this JSON file at shutdown — the offline "
                   "input to the static-vs-runtime lock-graph "
                   "cross-check (docs/ANALYSIS.md).")
@click.option("--request-history", default=256, type=int,
              help="Terminal request-record retention ring behind "
                   "GET /requests/<id>: per-request causal timelines "
                   "(queue wait, admission slot, preemptions with "
                   "preemptor IDs, page waits, terminal cause), "
                   "newest N retained. 0 disables recording.")
@click.option("--stall-timeout", default=None, type=float,
              help="Arm the STALL WATCHDOG: when work exists but no "
                   "decode-step boundary completes for this many "
                   "seconds (or a queued request ages past 4x its "
                   "class queue deadline), write a one-shot "
                   "diagnostic bundle (--stall-dir) — state "
                   "snapshot, trace tail, thread stacks — and bump "
                   "ptpu_serving_stalls_total. Unset = off.")
@click.option("--stall-dir", default=".", type=click.Path(),
              help="With --stall-timeout: directory stall bundles "
                   "(stall_<n>_<pid>.json) are written to.")
@click.option("--forensics/--no-forensics", "forensics",
              default=True,
              help="Tail-latency forensics (docs/SERVING.md): the "
                   "per-request phase ledger, histogram exemplars, "
                   "and the anomaly sentry behind GET /anomalies. "
                   "ON by default (<=3% contract, bench-pinned); "
                   "--no-forensics reduces it all to attribute "
                   "checks.")
@click.option("--exemplar-k", default=4, type=int,
              help="Request-ID exemplars retained per latency "
                   "histogram bucket (OpenMetrics suffixes on "
                   "/metrics + GET /debug/exemplars). 0 disables "
                   "exemplars only.")
@click.option("--forensics-dir", default=None, type=click.Path(),
              help="Arm per-episode anomaly bundles: first "
                   "detection per episode writes "
                   "anomaly_<n>_<pid>.json (finding, state, the "
                   "flagged window's exemplar records, trace tail) "
                   "here. Unset = findings/counters only, no "
                   "bundles.")
@click.option("--fault-plan", "fault_plan_path", default=None,
              type=click.Path(exists=True),
              help="CHAOS TESTING: arm the deterministic seeded "
                   "fault-injection harness from a JSON plan "
                   "(serving/faults.py — sites: step/page_alloc/"
                   "slow_step/engine_death/prefix_store/"
                   "socket_reset/telemetry).  Injected faults "
                   "exercise the containment ladder: bounded step "
                   "retries, quarantine bisection (the poisoned "
                   "request alone fails 500 poisoned_request), "
                   "supervised engine restart with requeue-and-"
                   "resume, and the crash-storm circuit breaker. "
                   "Unset (default): zero probes armed.")
@click.option("--no-supervise", is_flag=True, default=False,
              help="Disable the engine crash supervisor (an engine "
                   "crash then fails every in-flight request "
                   "instead of restarting with token-identical "
                   "requeue-and-resume — the pre-crash-only "
                   "behavior; debugging aid).")
@click.option("--cpu", is_flag=True, default=False)
def serve(model_name, host, port, checkpoint, int8_weights, int8_kv,
          kv_ring, kv_ring_slack, prefix_cache, max_batch, batching,
          n_slots, queue_depth, prefill_chunk, decode_window,
          mesh_arg, kv_paged, kv_page_tokens, kv_pages,
          kv_lazy, kv_host_spill_bytes,
          prefix_fetch, prefix_fetch_timeout,
          prefix_fetch_min_tokens, prefix_fetch_remat_ratio,
          role,
          default_priority, batch_queue_depth, queue_deadline_ms,
          batch_queue_deadline_ms, slo_ttft_ms, request_timeout,
          draft_model, draft_checkpoint, spec_k, trace_buffer,
          trace_file, profile_dir, profile_every, profile_steps,
          access_log, sanitize, sanitize_max_hold, sanitize_report,
          request_history,
          stall_timeout, stall_dir, forensics, exemplar_k,
          forensics_dir, fault_plan_path, no_supervise,
          cpu):
    """Serve a zoo model over HTTP (/healthz, /info, /metrics,
    /generate, /prefill — the last registers a prompt prefix whose
    prefill later /generate requests skip; /trace exports the
    telemetry ring as Chrome trace JSON, and /profile/start|stop
    drives on-demand jax.profiler traces when --profile-dir is set).

    The reference's `V1Service` schedules an opaque serving container;
    here the framework ships the model server itself (stdlib HTTP, jit
    compile cache, int8 serving flags — see the serving package).

    Greedy AND sampled traffic runs through the continuous-batching
    engine by default: a fixed pool of decode slots with
    step-boundary admission, eos-eviction, interleaved chunked
    prefill, and 429 backpressure once the admission queue fills
    (--batching selects the legacy coalescing or serialized baselines
    for A/Bs).  Sampled slots draw from position-keyed PRNG streams —
    a request's tokens depend on its (seed, token index) only, never
    on what else shares the pool — so responses are reproducible
    under any concurrency.  Beam/speculative requests decode solo.

    Requests are cancellable, deadline-bearing, and prioritized
    (docs/SERVING.md "Request lifecycle"): client disconnects and
    {"deadline_ms": N} expiries evict their slots at the next step
    boundary; {"priority": "interactive"|"batch"} picks the class
    queue; --slo-ttft-ms arms batch preemption with token-identical
    resume; per-class queue deadlines shed unstartable requests with
    503; and POST /drain stops admission, finishes in-flight work,
    and turns /healthz readiness off.
    """
    import jax

    if cpu:
        jax.config.update("jax_platforms", "cpu")
    from polyaxon_tpu.config import enable_compilation_cache

    enable_compilation_cache(names_in_key=bool(profile_dir))
    from polyaxon_tpu.serving import (ModelServer,
                                      PrefixFetchPolicy,
                                      make_server)

    if draft_checkpoint and not draft_model:
        # pre-checkable usage error: fail before paying the full
        # target build (checkpoint restore can take minutes)
        raise click.ClickException(
            "--draft-checkpoint requires --draft-model")
    if trace_buffer < 0:
        # same fail-fast contract: no model build for a bad flag
        raise click.ClickException("--trace-buffer must be >= 0")
    if profile_every < 0:
        raise click.ClickException("--profile-every must be >= 0")
    if profile_steps < 1:
        raise click.ClickException("--profile-steps must be >= 1")
    if profile_every and not profile_dir:
        raise click.ClickException(
            "--profile-every needs --profile-dir (the flight "
            "recorder writes jax.profiler windows there)")
    if profile_every and batching != "continuous":
        raise click.ClickException(
            "--profile-every requires --batching continuous (the "
            "flight recorder windows decode-step boundaries)")
    if sanitize_max_hold is not None and not sanitize:
        raise click.ClickException(
            "--sanitize-max-hold requires --sanitize")
    if sanitize_report is not None and not sanitize:
        raise click.ClickException(
            "--sanitize-report requires --sanitize")
    if request_history < 0:
        raise click.ClickException("--request-history must be >= 0")
    if stall_timeout is not None and stall_timeout <= 0:
        raise click.ClickException("--stall-timeout must be > 0")
    if stall_timeout is not None and batching != "continuous":
        raise click.ClickException(
            "--stall-timeout requires --batching continuous (the "
            "watchdog monitors decode-step boundaries)")
    fault_plan = None
    if fault_plan_path is not None:
        # Parse + validate the plan BEFORE the model build (the
        # fail-fast contract): a typo'd fault site must not cost a
        # checkpoint restore.
        from polyaxon_tpu.serving import FaultPlan

        try:
            fault_plan = FaultPlan.load(fault_plan_path)
        except (ValueError, OSError) as e:
            raise click.ClickException(
                f"--fault-plan {fault_plan_path}: {e}")
    for name, v in (("--queue-deadline-ms", queue_deadline_ms),
                    ("--batch-queue-deadline-ms",
                     batch_queue_deadline_ms),
                    ("--slo-ttft-ms", slo_ttft_ms)):
        if v is not None and v < 1:
            raise click.ClickException(f"{name} must be >= 1")
    if request_timeout is not None and request_timeout <= 0:
        raise click.ClickException("--request-timeout must be > 0")
    # Paged-KV flag validation: fail fast, before the model build.
    if kv_page_tokens < 8:
        raise click.ClickException("--kv-page-tokens must be >= 8")
    if kv_pages is not None and kv_pages < 1:
        raise click.ClickException("--kv-pages must be >= 1")
    if kv_paged and kv_ring:
        raise click.ClickException(
            "--kv-paged needs a plain/int8 max_position cache; it "
            "cannot combine with --kv-ring (the ring is already "
            "O(window))")
    if kv_paged and batching != "continuous":
        raise click.ClickException(
            "--kv-paged requires --batching continuous (paging is "
            "the engine's slot storage)")
    if kv_lazy and not kv_paged:
        raise click.ClickException(
            "--kv-lazy requires --kv-paged (lazy growth is a page-"
            "reservation policy)")
    if kv_host_spill_bytes < 0:
        raise click.ClickException(
            "--kv-host-spill-bytes must be >= 0")
    if kv_host_spill_bytes and not kv_paged:
        raise click.ClickException(
            "--kv-host-spill-bytes requires --kv-paged (the host "
            "tier spills page-pool payloads)")
    if prefix_fetch and not (kv_paged and kv_host_spill_bytes):
        raise click.ClickException(
            "--prefix-fetch requires --kv-paged and "
            "--kv-host-spill-bytes (wire-fetched payloads admit "
            "through the host spill tier)")
    # Role validation BEFORE the model build (fail-fast contract) —
    # mirror the ModelServer checks so a mis-flagged tier dies on
    # usage, not after a checkpoint restore.
    if role == "prefill" and not (kv_paged and kv_host_spill_bytes):
        raise click.ClickException(
            "--role prefill requires --kv-paged and "
            "--kv-host-spill-bytes (a prefill tier's product is "
            "admit-ready KV served over the /prefix/fetch lane)")
    if role == "decode" and not prefix_fetch:
        raise click.ClickException(
            "--role decode requires --prefix-fetch (the decode tier "
            "admits handed-off prefills through the wire-fetch "
            "lane)")
    mesh_spec = None
    if mesh_arg is not None:
        # Parse BEFORE the model build (fail-fast contract): a typo'd
        # axis or a size the local device count can't honor must not
        # cost a checkpoint restore.  Device-count validation happens
        # in ServingMesh (after `--cpu` had its chance to switch the
        # platform), but the spec grammar is checkable now.
        if batching != "continuous":
            raise click.ClickException(
                "--mesh requires --batching continuous (the mesh "
                "shards the engine's slot KV pools)")
        from polyaxon_tpu.serving.meshed import MeshError, parse_mesh

        try:
            mesh_spec = parse_mesh(mesh_arg)
        except MeshError as e:
            raise click.ClickException(str(e))
    try:
        # Shared validation with the server/library (_check_spec_k):
        # one message for a bad --spec-k on every surface.
        from polyaxon_tpu.models.generate import _check_spec_k

        _check_spec_k(spec_k)
    except ValueError as e:
        raise click.ClickException(str(e))
    model, variables, cast_bytes = _build_serving_model(
        model_name, 1, checkpoint, int8_kv, int8_weights,
        kv_ring=kv_ring, kv_ring_slack=kv_ring_slack)
    from polyaxon_tpu.serving.slots import pool_refusal

    # A speculative option in play: a draft model, or --spec-k given.
    speculative = bool(draft_model) or click.get_current_context() \
        .get_parameter_source("spec_k").name != "DEFAULT"
    draft = draft_vars = None
    if draft_model:
        # The draft mirrors the target's cache mode: a standard-cache
        # draft would re-impose the max_position bound --kv-ring
        # exists to lift.
        draft, draft_vars, draft_cast = _build_serving_model(
            draft_model, 1, draft_checkpoint, int8_kv, int8_weights,
            kv_ring=kv_ring, kv_ring_slack=kv_ring_slack)
        cast_bytes += draft_cast
    refusal = pool_refusal((model, draft), paged=kv_paged,
                           meshed=mesh_spec is not None,
                           speculative=speculative)
    if refusal:
        raise click.ClickException(f"{model_name}: {refusal}")
    from polyaxon_tpu.serving.meshed import MeshError

    try:
        ms = ModelServer(model, variables, model_name=model_name,
                         max_batch=max_batch, batching=batching,
                         n_slots=n_slots, queue_depth=queue_depth,
                         prefill_chunk=prefill_chunk,
                         decode_window=decode_window,
                         mesh=mesh_spec,
                         kv_paged=kv_paged,
                         kv_page_tokens=kv_page_tokens,
                         kv_pages=kv_pages,
                         kv_lazy=kv_lazy,
                         kv_host_spill_bytes=kv_host_spill_bytes,
                         prefix_fetch=prefix_fetch,
                         prefix_fetch_policy=PrefixFetchPolicy(
                             min_tokens=prefix_fetch_min_tokens,
                             remat_ratio=prefix_fetch_remat_ratio)
                         if prefix_fetch else None,
                         prefix_fetch_timeout_s=prefix_fetch_timeout,
                         role=role,
                         default_priority=default_priority,
                         batch_queue_depth=batch_queue_depth,
                         queue_deadline_s=queue_deadline_ms / 1e3
                         if queue_deadline_ms is not None else None,
                         batch_queue_deadline_s=batch_queue_deadline_ms
                         / 1e3 if batch_queue_deadline_ms is not None
                         else None,
                         slo_ttft_s=slo_ttft_ms / 1e3
                         if slo_ttft_ms is not None else None,
                         request_timeout_s=request_timeout,
                         prefix_cache=prefix_cache,
                         draft_model=draft, draft_variables=draft_vars,
                         weights_cast_bytes=cast_bytes,
                         spec_k=spec_k,
                         trace_buffer=trace_buffer,
                         profile_dir=profile_dir,
                         profile_every=profile_every,
                         profile_steps=profile_steps,
                         access_log=access_log,
                         sanitize=sanitize,
                         sanitize_max_hold_s=sanitize_max_hold,
                         sanitize_report=sanitize_report,
                         request_history=request_history,
                         stall_timeout_s=stall_timeout,
                         stall_dir=stall_dir,
                         forensics=forensics,
                         exemplar_k=exemplar_k,
                         forensics_dir=forensics_dir,
                         fault_plan=fault_plan,
                         supervise=not no_supervise,
                         info={**({"int8_weights": True}
                                  if int8_weights else {}),
                               **({"int8_kv": True} if int8_kv else {}),
                               **({"kv_ring": True} if kv_ring else {}),
                               **({"kv_page_tokens": kv_page_tokens}
                                  if kv_paged else {}),
                               **({"kv_lazy_mode": True}
                                  if kv_lazy else {}),
                               **({"draft_model": draft_model}
                                  if draft_model else {})})
    except MeshError as e:
        # Mesh validation (device count, head/expert divisibility)
        # fails AFTER the model build by necessity — it needs the
        # model config — but still deserves the clean usage-error
        # surface.
        raise click.ClickException(str(e))
    try:
        srv = make_server(host, port, ms)
    except OSError as e:
        raise click.ClickException(
            f"cannot bind {host}:{port}: {e}")
    click.echo(f"serving {model_name} on http://{host}:"
               f"{srv.server_address[1]}")
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        srv.shutdown()
    finally:
        ms.close()
        if trace_file:
            # Shutdown span dump, through the tracking stack's async
            # writer (telemetry.dump_spans_jsonl) — the offline twin
            # of GET /trace for post-mortem trace_report.py analysis.
            from polyaxon_tpu.serving.telemetry import \
                dump_spans_jsonl

            n = dump_spans_jsonl(ms.telemetry, trace_file)
            click.echo(f"wrote {n} trace events to {trace_file}",
                       err=True)


@cli.command()
@click.option("--host", default="127.0.0.1")
@click.option("--port", default=8100, type=int)
@click.option("--replica", "replicas", multiple=True, required=True,
              help="Replica endpoint (host:port or http://host:port);"
                   " repeat per replica.")
@click.option("--probe-interval", default=0.5, type=float,
              help="Seconds between /healthz probe rounds.")
@click.option("--probe-timeout", default=2.0, type=float,
              help="Per-probe socket timeout (a timeout-less probe "
                   "is how a hung replica wedges the router).")
@click.option("--down-after", default=2, type=int,
              help="Consecutive transport failures that trip a "
                   "replica out of rotation.")
@click.option("--cooldown", default=1.0, type=float,
              help="Seconds out of rotation before the half-open "
                   "re-admission probe.")
@click.option("--retry-ratio", default=0.1, type=float,
              help="Retry-budget refill per live request (retries + "
                   "hedges can never exceed this fraction of "
                   "traffic plus --retry-burst).")
@click.option("--retry-burst", default=8.0, type=float,
              help="Retry-budget bucket capacity (the cold-start "
                   "failover headroom).")
@click.option("--max-attempts", default=3, type=int,
              help="Replica attempts per request (first + "
                   "failovers).")
@click.option("--request-timeout", default=120.0, type=float,
              help="Per-attempt read timeout / default request "
                   "deadline, seconds.")
@click.option("--hedge", default="off",
              help="'off', 'p99' (duplicate a request sitting past "
                   "the sliding p99 watermark), or a fixed "
                   "threshold in seconds.")
@click.option("--hedge-min", default=0.2, type=float,
              help="Hedge watermark floor, seconds.")
@click.option("--affinity/--no-affinity", default=True,
              help="Radix-prefix affinity: route a request to the "
                   "replica whose store holds its registered "
                   "prefix (never beats health).")
@click.option("--prefix-handoff/--no-prefix-handoff", default=True,
              help="Drain-time cache migration: a rolling restart "
                   "pushes the drainee's hot host-tier prefix "
                   "entries to its router-chosen successor (POST "
                   "/prefix/handoff) before the flush.  Off = a "
                   "restart is a cache flush (the per-replica "
                   "baseline).")
@click.option("--disagg-min-tokens", default=16, type=int,
              help="Disaggregated serving: prompts at or above this "
                   "length take the two-stage prefill->decode "
                   "schedule when the fleet runs a dedicated "
                   "--role prefill tier (shorter prompts decode "
                   "locally — the handoff would cost more than the "
                   "prefill).")
@click.option("--rebalance-every", default=0.0, type=float,
              help="Seconds between cadenced POST "
                   "/fleet/prefix/rebalance passes, driven off the "
                   "federated kv_host_* gauges (runs only while "
                   ">=2 replicas hold host-tier entries; "
                   "one-flight; failures counted, never fatal).  "
                   "0 = operator trigger only (default).")
@click.option("--min-ready", default=1, type=int,
              help="Rolling restart never drops the ready-replica "
                   "count below this.")
@click.option("--fleet-fault-plan", default=None, type=click.Path(),
              help="Seeded fleet chaos plan (JSON; replica_kill/"
                   "replica_hang/replica_slow sites) — local "
                   "replicas only.")
@click.option("--request-history", default=256, type=int,
              help="Router-side request-span retention ring "
                   "(GET /fleet/requests/<id> — the cross-replica "
                   "stitched timeline); 0 disables.")
@click.option("--slo", default=None,
              help="Declared objectives evaluated over a sliding "
                   "window of the router's own accounting, e.g. "
                   "'availability=99.9,ttft_p99_ms=1000'; exported "
                   "as ptpu_router_slo_burn_rate{objective=}.")
@click.option("--slo-window", default=512, type=int,
              help="Sliding-window size (requests) the SLO burn "
                   "rates are computed over.")
@click.option("--forensics/--no-forensics", "forensics",
              default=True,
              help="Router-side tail-latency forensics: the "
                   "per-request router phase ledger (route_pick/"
                   "replica_attempt/prefill_remote/retry_backoff), "
                   "its anomaly sentry (GET /anomalies), and the "
                   "fleet-merged GET /fleet/anomalies ranking.")
@click.option("--forensics-dir", default=None, type=click.Path(),
              help="Arm per-episode router anomaly bundles "
                   "(anomaly_<n>_<pid>.json). Unset = findings/"
                   "counters only.")
def route(host, port, replicas, probe_interval, probe_timeout,
          down_after, cooldown, retry_ratio, retry_burst,
          max_attempts, request_timeout, hedge, hedge_min, affinity,
          prefix_handoff, disagg_min_tokens, rebalance_every,
          min_ready, fleet_fault_plan,
          request_history, slo, slo_window, forensics,
          forensics_dir):
    """Run the replica ROUTER tier in front of N `ptpu serve`
    replicas (docs/SERVING.md "Fleet").

    The router probes each replica's /healthz (503 draining/
    engine_down takes it out of rotation; recovery re-admits it
    after a half-open success probe), balances by least-outstanding
    with radix-prefix affinity, fails replica deaths over inside a
    bounded retry budget with jittered backoff, optionally hedges
    requests past the p99 watermark (first winner cancels the
    loser), and rolls restarts via POST /fleet/restart without
    dropping below --min-ready ready replicas.

    Fleet observability (docs/SERVING.md "Fleet observability"):
    GET /fleet/requests/<id> stitches the router's request spans
    with every involved replica's history record into one causal
    timeline; GET /fleet/metrics federates every replica's /metrics
    with replica= labels and fleet rollups; --slo arms router-side
    error-budget burn-rate gauges.
    """
    from polyaxon_tpu.serving import (ReplicaRouter,
                                      make_router_server)

    try:
        router = ReplicaRouter(
            list(replicas),
            probe_interval_s=probe_interval,
            probe_timeout_s=probe_timeout,
            down_after=down_after,
            cooldown_s=cooldown,
            retry_ratio=retry_ratio,
            retry_burst=retry_burst,
            max_attempts=max_attempts,
            request_timeout_s=request_timeout,
            hedge=hedge,
            hedge_min_s=hedge_min,
            affinity=affinity,
            prefix_handoff=prefix_handoff,
            disagg_min_tokens=disagg_min_tokens,
            rebalance_every_s=rebalance_every,
            min_ready=min_ready,
            fleet_faults=fleet_fault_plan,
            request_history=request_history,
            slo=slo,
            slo_window=slo_window,
            forensics=forensics,
            forensics_dir=forensics_dir)
    except ValueError as e:
        raise click.ClickException(str(e))
    try:
        srv = make_router_server(host, port, router)
    except OSError as e:
        router.close()
        raise click.ClickException(
            f"cannot bind {host}:{port}: {e}")
    click.echo(f"routing {len(replicas)} replica(s) on "
               f"http://{host}:{srv.server_address[1]}")
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        srv.shutdown()
    finally:
        router.close()


# ---------------------------------------------------------------------------
# ops
# ---------------------------------------------------------------------------


@cli.group()
def ops():
    """Inspect and manage runs."""


def _store():
    from polyaxon_tpu.client.run_client import get_client

    return get_client()


@ops.command(name="ls")
@click.option("--project", default=None)
@click.option("--query", "-q", default=None,
              help='Filter, e.g. "status:running, metrics.loss:<0.1".')
@click.option("--sort", default="-created_at")
@click.option("--limit", default=20, type=int)
@click.option("--offset", default=0, type=int)
def ops_ls(project, query, sort, limit, offset):
    """List runs."""
    from polyaxon_tpu.client.store import StoreError
    from polyaxon_tpu.query import QueryError

    try:
        runs = _store().list_runs(project=project, query=query, sort=sort,
                                  limit=limit, offset=offset)
    except (QueryError, StoreError) as e:
        raise click.ClickException(str(e))
    if not runs:
        click.echo("No runs found.")
        return
    fmt = "{:<14} {:<24} {:<12} {:<11} {:<12} {:>3} {:>9}"
    click.echo(fmt.format("UUID", "NAME", "KIND", "STATUS", "QUEUE",
                          "PRI", "DURATION"))
    for r in runs:
        dur = r.get("duration")
        click.echo(fmt.format(
            r["uuid"], (r.get("name") or "")[:24], str(r.get("kind") or "-"),
            r.get("status") or "-", (r.get("queue") or "-")[:12],
            str(r.get("priority") or 0), f"{dur:.1f}s" if dur else "-",
        ))


@ops.command(name="get")
@click.argument("run_uuid")
def ops_get(run_uuid):
    """Show one run's record (+ heartbeat age for running runs)."""
    record = _get_run_or_fail(run_uuid)
    if record.get("status") == "running":
        try:
            beat = _store().heartbeat_at(run_uuid)
        except Exception:  # noqa: BLE001 - informational only
            beat = None
        if beat is not None:
            import time as _time

            # Clamp: in API mode `beat` is the server's clock; a few
            # seconds of client/server skew must not print a negative
            # age.
            record = {**record,
                      "heartbeat_age_s":
                          max(0.0, round(_time.time() - beat, 1))}
    click.echo(json.dumps(record, indent=2, default=str))


def _get_run_or_fail(run_uuid: str) -> Dict[str, Any]:
    from polyaxon_tpu.client.store import StoreError

    try:
        return _store().get_run(run_uuid)
    except StoreError as e:
        raise click.ClickException(str(e))


@ops.command(name="compare")
@click.argument("run_uuids", nargs=-1, required=True)
def ops_compare(run_uuids):
    """Compare runs side by side: status, inputs, last metrics."""
    from polyaxon_tpu.client.store import StoreError

    store = _store()
    records, metrics = [], []
    for u in run_uuids:
        try:
            records.append(store.get_run(u))
        except StoreError as e:
            raise click.ClickException(str(e))
        try:
            metrics.append(store.last_metrics(u))
        except Exception:  # noqa: BLE001 - missing metrics show as '-'
            metrics.append({})

    def fmt(value):
        return f"{value:.6g}" if isinstance(value, float) else str(value)

    input_keys = sorted({k for r in records
                         for k in (r.get("inputs") or {})})
    metric_keys = sorted({k for m in metrics for k in m})
    rows: List[Tuple[str, List[str]]] = [
        ("status", [r.get("status") or "-" for r in records]),
        ("duration", [f"{r['duration']:.1f}s" if r.get("duration")
                      else "-" for r in records]),
    ]
    rows += [(f"in:{k}", [fmt((r.get("inputs") or {}).get(k, "-"))
                          for r in records]) for k in input_keys]
    rows += [(f"metric:{k}", [fmt(m.get(k, "-")) for m in metrics])
             for k in metric_keys]

    label_w = max(16, max(len(k) for k, _ in rows) + 1)
    width = 22
    header = " ".join(f"{u[:12]:>{width}}" for u in run_uuids)
    click.echo(f"{'':<{label_w}}{header}")
    for key, values in rows:
        cells = " ".join(f"{v:>{width}}" for v in values)
        click.echo(f"{key:<{label_w}}{cells}")


@ops.command(name="logs")
@click.argument("run_uuid")
@click.option("--replica", default=None)
@click.option("--tail", default=None, type=int)
@click.option("--follow", "-f", is_flag=True, default=False,
              help="Stream new log lines until the run finishes.")
def ops_logs(run_uuid, replica, tail, follow):
    """Print (or follow) a run's logs."""
    import time as _time

    from polyaxon_tpu.lifecycle import is_done
    from polyaxon_tpu.scheduler.api import ControlPlane

    _get_run_or_fail(run_uuid)
    store = _store()
    if not follow:
        click.echo(store.read_logs(run_uuid, replica=replica, tail=tail))
        return
    # Per-replica offset streaming (offsets are per file, so multiple
    # replicas can't shift each other's positions).  API store speaks
    # the protocol natively; the file store goes through an in-process
    # ControlPlane shim.
    reader = store if hasattr(store, "read_logs_multi") else \
        ControlPlane(store)
    offsets: Dict[str, int] = {}

    def drain() -> None:
        out = reader.read_logs_multi(run_uuid, offsets)
        replicas = out.get("replicas", {})
        many = len(replicas) > 1 or (replica is None and len(offsets) > 1)
        for rep in sorted(replicas):
            if replica is not None and rep != replica:
                offsets[rep] = replicas[rep]["offset"]
                continue
            chunk = replicas[rep]["logs"]
            offsets[rep] = replicas[rep]["offset"]
            if not chunk:
                continue
            if many:
                for line in chunk.splitlines():
                    click.echo(f"[{rep}] {line}")
            else:
                click.echo(chunk, nl=False)

    while True:
        drain()
        status = store.get_run(run_uuid).get("status")
        if is_done(status):
            drain()  # final read: lines flushed just before completion
            break
        _time.sleep(1.0)


@ops.command(name="statuses")
@click.argument("run_uuid")
def ops_statuses(run_uuid):
    """Print a run's status history."""
    _get_run_or_fail(run_uuid)
    for c in _store().get_statuses(run_uuid):
        line = f"{c.last_transition_time:.0f}  {c.type:<16} {c.reason or ''}"
        if c.message:
            line += f"  {c.message}"
        click.echo(line)


@ops.command(name="artifacts")
@click.argument("run_uuid")
def ops_artifacts(run_uuid):
    """List a run's artifact tree and lineage."""
    _get_run_or_fail(run_uuid)
    store = _store()
    root = store.artifacts_path(run_uuid)
    for dirpath, _, files in os.walk(root):
        for fname in files:
            path = os.path.join(dirpath, fname)
            click.echo(os.path.relpath(path, root))
    lineage = store.get_lineage(run_uuid)
    if lineage:
        click.echo("--- lineage ---")
        for rec in lineage:
            click.echo(f"{rec.get('kind'):<10} {rec.get('name')}")


@ops.command(name="metrics")
@click.argument("run_uuid")
@click.option("--name", default=None, help="One metric series (else last values).")
def ops_metrics(run_uuid, name):
    """Show tracked metrics."""
    _get_run_or_fail(run_uuid)
    store = _store()
    if name:
        for e in store.read_events(run_uuid, "metric", name):
            click.echo(f"step={e.get('step')} value={e.get('value')}")
    else:
        for metric, value in sorted(store.last_metrics(run_uuid).items()):
            click.echo(f"{metric}: {value}")


def _reap_local_service(store, run_uuid: str) -> bool:
    """Kill a locally-spawned service (runner.local._run_service
    records its pid/session in meta_info) and mark it stopped.  The
    k8s path doesn't need this — the operator reconciles STOPPING —
    but a local detached service has no operator watching it."""
    try:
        rec = store.get_run(run_uuid)
    except Exception:
        return False
    svc = (rec.get("meta_info") or {}).get("service") or {}
    pid = svc.get("pid")
    if not pid or svc.get("host") not in (None, "127.0.0.1"):
        return False
    import signal

    try:
        os.killpg(int(pid), signal.SIGTERM)
    except ProcessLookupError:
        pass  # already gone — marking stopped is correct
    except PermissionError:
        # We could NOT signal it (pid reuse across uids, etc.) —
        # claiming "stopped" would strand a live orphan with a
        # terminal-status record no second `ops stop` can fix.
        click.echo(f"cannot signal service pid {pid} "
                   f"(permission denied); not marking stopped",
                   err=True)
        return False
    store.set_status(run_uuid, "stopped", reason="CliStop", force=True)
    return True


@ops.command(name="stop")
@click.argument("run_uuid")
def ops_stop(run_uuid):
    """Request a run stop."""
    _get_run_or_fail(run_uuid)
    store = _store()
    ok = store.set_status(run_uuid, "stopping", reason="CliStop")
    if ok and _reap_local_service(store, run_uuid):
        click.echo("stopped (local service reaped)")
        return
    click.echo("stopping" if ok else "run is already done")


@ops.command(name="delete")
@click.argument("run_uuid")
@click.confirmation_option(prompt="Delete this run and its artifacts?")
def ops_delete(run_uuid):
    """Delete a run."""
    _get_run_or_fail(run_uuid)
    _store().delete_run(run_uuid)
    click.echo(f"deleted {run_uuid}")


@ops.command(name="restart")
@click.argument("run_uuid")
@click.option("--copy", "copy_artifacts", is_flag=True,
              help="Copy the original run's artifacts into the new run.")
def ops_restart(run_uuid, copy_artifacts):
    """Restart a run as a new run (optionally copying artifacts)."""
    record = _restart(run_uuid, copy_artifacts=copy_artifacts, resume=False)
    _echo_record(record)


@ops.command(name="resume")
@click.argument("run_uuid")
def ops_resume(run_uuid):
    """Resume a run: restart pointing at the SAME artifacts (latest
    checkpoint is picked up via {{ globals.run_artifacts_path }})."""
    record = _restart(run_uuid, copy_artifacts=True, resume=True)
    _echo_record(record)


def _restart(run_uuid: str, copy_artifacts: bool, resume: bool):
    import shutil

    from polyaxon_tpu.flow import V1Operation
    from polyaxon_tpu.runner import LocalExecutor

    record = _get_run_or_fail(run_uuid)
    content = record.get("content")
    if not content:
        raise click.ClickException(
            f"Run {run_uuid} stores no operation content; cannot restart")
    op = V1Operation.from_dict(content)
    # Sweep children were created with matrix stripped and their concrete
    # suggestion stored in meta_info — replay it.
    matrix_values = (record.get("meta_info") or {}).get("matrix_values")
    meta = {"restarted_from": run_uuid, "is_resume": resume}
    if matrix_values:
        meta["matrix_values"] = matrix_values

    if os.environ.get("POLYAXON_TPU_HOST"):
        # API mode: resubmit to the control plane; the agent executes.
        store = _store()
        new = store.create_run(
            name=record.get("name"), project=record.get("project"),
            content=content, kind=record.get("kind"), meta_info=meta,
            managed_by="agent",
            # keep queue routing/priority: a restarted tpu-v5e run must
            # stay claimable by queue-scoped agents
            queue=record.get("queue"),
            priority=record.get("priority") or 0,
        )
        store.set_status(new["uuid"], "queued", reason="CliRestart",
                         force=True)
        return store.get_run(new["uuid"])

    executor = LocalExecutor(project=record.get("project") or "default")
    new_uuid = executor.create_run(op, meta_info=meta)
    if copy_artifacts:
        src = executor.store.artifacts_path(run_uuid)
        dst = executor.store.artifacts_path(new_uuid)
        if os.path.isdir(src):
            shutil.copytree(src, dst, dirs_exist_ok=True)
    try:
        return executor.run_operation(op, run_uuid=new_uuid,
                                      matrix_values=matrix_values)
    except Exception as e:
        raise click.ClickException(f"Restart failed: {e}")


# ---------------------------------------------------------------------------
# config / check / version
# ---------------------------------------------------------------------------


@cli.command()
@click.argument("paths", nargs=-1, type=click.Path(exists=True))
@click.option("-f", "--file", "files", multiple=True,
              type=click.Path(),
              help="Validate polyaxonfile(s) instead of running the "
                   "static analyzer.")
@click.option("-P", "--param", "params", multiple=True)
@click.option("--format", "fmt", type=click.Choice(["text", "json"]),
              default="text", help="Finding output format.")
@click.option("--baseline", "baseline_path", default=None,
              type=click.Path(),
              help="Baseline file of accepted findings (default: the "
                   "committed polyaxon_tpu/analysis/baseline.json).")
@click.option("--update-baseline", is_flag=True, default=False,
              help="Rewrite the baseline from the current findings "
                   "(stable sort; justifications preserved, new "
                   "entries get a TODO placeholder to fill in).")
@click.option("--changed", "changed_ref", is_flag=False,
              flag_value="HEAD", metavar="[REF]",
              # No `default=`: click only treats the value as optional
              # (bare `--changed` -> flag_value) when the default is
              # left UNSET; passing default=None re-arms the
              # requires-an-argument parse.  The resolved default is
              # still None.
              help="Incremental mode: lint only files changed vs a "
                   "git ref (default HEAD), plus untracked files — "
                   "identical findings/exit semantics to a full run "
                   "on those files.  Fast enough for a pre-commit "
                   "hook.  Use --changed=REF when followed by PATHS "
                   "(a bare ref would swallow the next argument).")
@click.option("--dump-lock-graph", "lock_graph_path", default=None,
              type=click.Path(),
              help="Write the canonical static lock-order graph "
                   "(the committed analysis/lockorder.json artifact) "
                   "to this path and exit.")
def check(paths, files, params, fmt, baseline_path, update_baseline,
          changed_ref, lock_graph_path):
    """Validate a polyaxonfile (-f), or run the JAX-aware static
    analyzer over PATHS (default: polyaxon_tpu/).

    The analyzer machine-checks the serving stack's own invariants —
    per-module rule families RNG-DET, LOCK-HOLD, JIT-PURITY,
    HOST-SYNC, EXC-SWALLOW, ... plus the whole-program concurrency
    families LOCK-ORDER (static lock-acquisition-graph cycles =
    potential deadlocks, with witness paths) and THREAD-SHARE
    (attributes written from several thread roots with no common
    lock) — docs/ANALYSIS.md has the catalog.  Exit status is
    non-zero when findings exist beyond the committed baseline;
    suppress locally-justified findings with `# ptpu: ignore[RULE]`
    (or `# ptpu: lockfree[reason]` for by-design lock-free sharing),
    baseline historically-justified ones with --update-baseline plus
    a written justification.
    """
    if files:
        from polyaxon_tpu.polyaxonfile import check_polyaxonfile
        from polyaxon_tpu.polyaxonfile.reader import PolyaxonfileError

        try:
            op = check_polyaxonfile(list(files),
                                    params=_parse_params(params))
        except (PolyaxonfileError, ValueError) as e:
            raise click.ClickException(str(e))
        kind = (getattr(op.component.run, "kind", "?")
                if op.has_component else "ref")
        click.echo(f"Valid operation: name={op.name!r} kind={kind}"
                   + (f" matrix={op.matrix.kind}" if op.matrix else ""))
        return

    if params:
        # -P only means something to polyaxonfile validation: a CI
        # line that lost its -f must fail loudly, not silently run
        # the analyzer and report lint status as file validity.
        raise click.ClickException(
            "-P/--param requires -f (polyaxonfile validation); "
            "the static analyzer takes PATHS only")

    import polyaxon_tpu as _pkg
    from polyaxon_tpu.analysis import (DEFAULT_BASELINE,
                                       apply_baseline, check_paths,
                                       load_baseline, save_baseline)
    from polyaxon_tpu.analysis.checker import iter_py_files

    # Findings and baseline entries are keyed by paths relative to
    # the REPO root (the directory holding the package), never the
    # cwd — `ptpu check` must match the committed baseline from any
    # working directory.
    root = os.path.dirname(
        os.path.dirname(os.path.abspath(_pkg.__file__)))
    target = list(paths) or [os.path.join(root, "polyaxon_tpu")]
    for p in target:
        if not os.path.exists(p):
            raise click.ClickException(f"no such path: {p}")

    if lock_graph_path is not None:
        from polyaxon_tpu.analysis import lockgraph as _lockgraph

        sources = {}
        for p in iter_py_files(target):
            rel = os.path.relpath(os.path.abspath(p), root).replace(
                os.sep, "/")
            if _lockgraph.in_program_scope(rel):
                with open(p, encoding="utf-8") as fh:
                    sources[rel] = fh.read()
        graph = _lockgraph.build_lock_graph(
            _lockgraph.build_model(sources))
        with open(lock_graph_path, "w", encoding="utf-8") as fh:
            json.dump(_lockgraph.canonical_graph(graph), fh, indent=1,
                      sort_keys=True)
            fh.write("\n")
        click.echo(f"wrote {len(graph.edges)} lock-order edges to "
                   f"{lock_graph_path}")
        return

    if changed_ref is not None:
        # Incremental mode: the checked file set becomes "changed vs
        # REF (plus untracked)" intersected with the target paths.
        # Everything downstream — per-module rules, the program
        # families over the in-scope subset, baseline, exit status —
        # is exactly a full run on those files.
        import subprocess

        def _git(*args):
            return subprocess.run(["git", *args], cwd=root,
                                  capture_output=True, text=True)

        diff = _git("diff", "--name-only", changed_ref, "--", "*.py")
        if diff.returncode != 0:
            raise click.ClickException(
                f"git diff vs {changed_ref!r} failed: "
                f"{diff.stderr.strip() or diff.stdout.strip()}")
        names = set(diff.stdout.split())
        untracked = _git("ls-files", "--others", "--exclude-standard",
                         "--", "*.py")
        if untracked.returncode == 0:
            names.update(untracked.stdout.split())
        roots_abs = [os.path.abspath(t) for t in target]
        target = []
        for name in sorted(names):
            p = os.path.join(root, name)
            if not (name.endswith(".py") and os.path.isfile(p)):
                continue            # deleted files have no findings
            ap = os.path.abspath(p)
            if any(ap == t or ap.startswith(t + os.sep)
                   for t in roots_abs):
                target.append(p)

    baseline_path = baseline_path or DEFAULT_BASELINE
    findings = check_paths(target, root=root)
    if update_baseline:
        previous = load_baseline(baseline_path)
        # Only the CHECKED paths' debt is rewritten: entries for
        # files outside this run's scope are preserved verbatim, so
        # `ptpu check some/subdir --update-baseline` can never drop
        # other files' entries (and their written justifications).
        checked = {
            os.path.relpath(os.path.abspath(f), root).replace(
                os.sep, "/")
            for f in iter_py_files(target)}
        entries = save_baseline(
            baseline_path, findings, previous=previous,
            preserve=[e for e in previous
                      if e["path"] not in checked])
        click.echo(f"wrote {len(entries)} baseline entries to "
                   f"{baseline_path}")
        return
    entries = load_baseline(baseline_path)
    new, stale = apply_baseline(findings, entries)
    if fmt == "json":
        click.echo(json.dumps({
            "checked_paths": target,
            "findings": [f.to_dict() for f in new],
            "baselined": len(findings) - len(new),
            "new": len(new),
            "stale_baseline_entries": stale,
        }, indent=1))
    else:
        for f in new:   # already stably sorted (path, line, rule)
            click.echo(f.render())
        for e in stale:
            click.echo(f"note: stale baseline entry (code fixed?): "
                       f"{e['rule']} {e['path']} [{e['func']}] — "
                       f"run --update-baseline to drop it", err=True)
        click.echo(f"{len(new)} new finding"
                   f"{'' if len(new) == 1 else 's'} "
                   f"({len(findings) - len(new)} baselined)")
    if new:
        raise SystemExit(1)


@cli.command()
@click.argument("url")
@click.option("--timeout", "timeout_s", default=5.0, type=float,
              help="Per-request HTTP timeout (seconds).")
@click.option("--format", "fmt", type=click.Choice(["text", "json"]),
              default="text", help="Report output format.")
def doctor(url, timeout_s, fmt):
    """Tail-latency forensics for a serving endpoint: fetch the
    anomaly-sentry findings from URL (a router — /fleet/anomalies —
    or a single replica — /anomalies), rank phase regressions, and
    print the exemplar request ids that resolve each one to a full
    per-attempt timeline via GET /fleet/requests/<id>."""
    import urllib.error
    import urllib.request

    base = url.rstrip("/")
    if not base.startswith(("http://", "https://")):
        base = "http://" + base

    def fetch(path):
        try:
            with urllib.request.urlopen(base + path,
                                        timeout=timeout_s) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, None
        except (OSError, ValueError) as e:
            raise click.ClickException(
                f"GET {base}{path} failed: {e}")

    # Router first; a replica answers 404 there, so fall back to its
    # own /anomalies (same findings shape, no source= attribution).
    status, body = fetch("/fleet/anomalies")
    source = "/fleet/anomalies"
    if status == 404 or not isinstance(body, dict):
        status, body = fetch("/anomalies")
        source = "/anomalies"
    if status != 200 or not isinstance(body, dict):
        raise click.ClickException(
            f"GET {base}{source} returned {status} "
            f"(forensics disabled on the target?)")
    if fmt == "json":
        click.echo(json.dumps({"url": base, "source": source,
                               **body}, indent=1))
        return
    findings = body.get("findings", [])
    click.echo(f"doctor {base} ({source})")
    for rid in body.get("fetch_errors", []):
        click.echo(f"  warning: replica {rid} did not answer "
                   f"/anomalies; its findings are absent", err=True)
    share = body.get("phase_share")
    if isinstance(share, dict) and share:
        # Single-replica report: one flat share dict; router report:
        # one dict per source.
        per_source = share if all(isinstance(v, dict)
                                  for v in share.values()) \
            else {"self": share}
        click.echo("phase shares (fraction of request wall time):")
        for src in sorted(per_source):
            shares = per_source[src]
            ranked = sorted(shares.items(),
                            key=lambda kv: -float(kv[1]))
            top = ", ".join(f"{ph}={float(v):.3f}"
                            for ph, v in ranked[:5] if float(v) > 0)
            click.echo(f"  {src:>12}: {top or '(no traffic)'}")
    if not findings:
        click.echo("no anomalies: every phase within its baseline "
                   "band (or the sentry is still building baselines)")
        return
    click.echo(f"{len(findings)} anomalous phase"
               f"{'' if len(findings) == 1 else 's'}, worst first:")
    for f in findings:
        src = f.get("source", "self")
        click.echo(
            f"  [{src}] {f.get('phase')}: share "
            f"{float(f.get('share', 0)):.3f} vs baseline "
            f"{float(f.get('baseline_ewma', 0)):.3f} "
            f"(band hi {float(f.get('band_hi', 0)):.3f}, score "
            f"{float(f.get('score', 0)):.3f}, window "
            f"{f.get('window')})")
        for rid in f.get("exemplars", []):
            click.echo(f"      exemplar {rid} -> GET "
                       f"{base}/fleet/requests/{rid}")
        if f.get("bundle"):
            click.echo(f"      bundle {f['bundle']}")
    raise SystemExit(1)


@cli.group()
def config():
    """Show/set client configuration."""


@config.command(name="show")
def config_show():
    import dataclasses

    from polyaxon_tpu.client.store import default_home
    from polyaxon_tpu.config import ClientConfig

    cfg = ClientConfig.load()
    click.echo(f"home: {default_home()}")
    for key, value in dataclasses.asdict(cfg).items():
        if key == "token" and value:
            value = "****"  # never echo secrets
        click.echo(f"{key}: {value}")


@config.command(name="set")
@click.argument("pairs", nargs=-1, required=True)
def config_set(pairs):
    """Persist config values: ptpu config set host=http://cp:8000."""
    from polyaxon_tpu.config import ClientConfig

    parsed = {}
    for pair in pairs:
        if "=" not in pair:
            raise click.ClickException(f"expected key=value, got {pair!r}")
        key, _, value = pair.partition("=")
        parsed[key.strip()] = value
    try:
        path = ClientConfig.set_file_values(parsed)
    except KeyError as e:
        raise click.ClickException(str(e))
    click.echo(f"saved {path}")


@config.command(name="get")
@click.argument("key")
def config_get(key):
    import dataclasses

    from polyaxon_tpu.config import ClientConfig

    cfg = dataclasses.asdict(ClientConfig.load())
    if key not in cfg:
        raise click.ClickException(
            f"unknown key {key!r}; known: {sorted(cfg)}")
    click.echo(cfg[key])


@cli.command()
def version():
    """Print versions (framework + runtime stack)."""
    click.echo(f"polyaxon-tpu {__version__}")
    try:
        import jax

        click.echo(f"jax {jax.__version__}")
    except ImportError:
        pass


@cli.command(name="port-forward")
@click.argument("run_uuid")
@click.option("--port", "-p", default=None, type=int,
              help="Local port (default: same as the service port).")
@click.option("--target", default=None,
              help="Override target host:port (default: the run's "
                   "recorded endpoint, else 127.0.0.1:<service port>).")
def port_forward(run_uuid, port, target):
    """Forward a local port to a service run (notebook/TensorBoard)."""
    import socket
    import socketserver
    import threading

    record = _get_run_or_fail(run_uuid)
    meta = record.get("meta_info") or {}
    if target is None:
        target = meta.get("endpoint")
    if target is None:
        # A locally-executed service records its live ports
        # (runner.local._run_service).
        svc = meta.get("service") or {}
        if svc.get("ports"):
            target = (f"{svc.get('host', '127.0.0.1')}:"
                      f"{svc['ports'][0]}")
    if target is None:
        content = record.get("content") or {}
        run_section = (content.get("component") or {}).get("run") or {}
        ports = run_section.get("ports") or []
        if not ports:
            raise click.ClickException(
                f"Run {run_uuid} declares no service ports; pass --target")
        target = f"127.0.0.1:{ports[0]}"
    host, _, tport = target.partition(":")
    tport = int(tport or 80)
    local_port = port or tport

    class Relay(socketserver.BaseRequestHandler):
        def handle(self):
            try:
                upstream = socket.create_connection((host, tport),
                                                    timeout=10)
            except OSError as e:
                self.request.close()
                click.echo(f"connect {host}:{tport} failed: {e}", err=True)
                return

            def pump(src, dst):
                try:
                    while True:
                        data = src.recv(65536)
                        if not data:
                            break
                        dst.sendall(data)
                except OSError:
                    pass
                finally:
                    # Half-close only: EOF on src ends THIS direction;
                    # the reverse pump keeps relaying the response.
                    try:
                        dst.shutdown(socket.SHUT_WR)
                    except OSError:
                        pass

            t = threading.Thread(target=pump,
                                 args=(upstream, self.request),
                                 daemon=True)
            t.start()
            pump(self.request, upstream)
            t.join(timeout=5)

    class Server(socketserver.ThreadingTCPServer):
        allow_reuse_address = True
        daemon_threads = True

    with Server(("127.0.0.1", local_port), Relay) as server:
        click.echo(f"forwarding 127.0.0.1:{local_port} -> {host}:{tport} "
                   "(ctrl-c to stop)")
        try:
            server.serve_forever()
        except KeyboardInterrupt:
            pass


# ---------------------------------------------------------------------------
# project
# ---------------------------------------------------------------------------


@cli.group()
def project():
    """Inspect projects (namespaces grouping runs)."""


@project.command(name="ls")
def project_ls():
    from collections import Counter

    counts = Counter(r.get("project") or "default"
                     for r in _store().list_runs())
    for name, n in sorted(counts.items()):
        click.echo(f"{name:<24} {n} runs")


@project.command(name="runs")
@click.argument("name")
@click.option("--limit", default=20, type=int)
def project_runs(name, limit):
    for r in _store().list_runs(project=name, limit=limit):
        click.echo(f"{r['uuid']}  {r.get('status', ''):<10} "
                   f"{r.get('name', '')}")


# ---------------------------------------------------------------------------
# auth
# ---------------------------------------------------------------------------


@cli.group()
def auth():
    """Authentication against the control plane."""


@auth.command(name="login")
@click.option("--token", prompt=True, hide_input=True,
              help="API token (prompted when omitted).")
@click.option("--host", default=None)
def auth_login(token, host):
    from polyaxon_tpu.config import ClientConfig

    values = {"token": token}
    if host:
        values["host"] = host
    ClientConfig.set_file_values(values)
    click.echo("logged in (token stored in home config)")


@auth.command(name="logout")
def auth_logout():
    from polyaxon_tpu.config import ClientConfig

    ClientConfig.unset_file_values(["token"])
    click.echo("logged out")


@auth.command(name="whoami")
def auth_whoami():
    from polyaxon_tpu.config import ClientConfig

    cfg = ClientConfig.load()
    click.echo(f"host: {cfg.host or '(local mode)'}")
    click.echo(f"token: {'set' if cfg.token else '(none)'}")


# ---------------------------------------------------------------------------
# admin
# ---------------------------------------------------------------------------


@cli.group()
def admin():
    """Deployment management."""


@admin.command(name="deploy")
@click.option("--namespace", default="polyaxon-tpu")
@click.option("--image", default="polyaxon-tpu/core:latest")
@click.option("--operator-image", default="polyaxon-tpu/operator:latest")
@click.option("--artifacts-claim", default=None)
@click.option("-o", "--output", default="-",
              help="Write manifests to a file ('-' = stdout).")
def admin_deploy(namespace, image, operator_image, artifacts_claim, output):
    """Render the k8s manifests for a full deployment (CRD, RBAC,
    control plane, agent, native operator)."""
    import yaml as _yaml

    from polyaxon_tpu.deploy import DeploymentConfig, render_all

    manifests = render_all(DeploymentConfig(
        namespace=namespace, image=image, operator_image=operator_image,
        artifacts_claim=artifacts_claim))
    text = "---\n".join(_yaml.safe_dump(m, sort_keys=False)
                        for m in manifests)
    if output == "-":
        click.echo(text)
    else:
        with open(output, "w") as f:
            f.write(text)
        click.echo(f"wrote {len(manifests)} manifests to {output}")


# ---------------------------------------------------------------------------
# control plane + agent services
# ---------------------------------------------------------------------------


@cli.command()
@click.option("--host", default="127.0.0.1")
@click.option("--port", default=8000, type=int)
@click.option("--schedules/--no-schedules", default=True,
              help="Also run the schedule-materializer loop.")
@click.option("--auth-token", default=None, envvar="POLYAXON_TPU_AUTH_TOKEN",
              help="Require this bearer token on every request.")
def server(host, port, schedules, auth_token):
    """Serve the control plane API (runs DB, queue, streams,
    dashboard at /ui, Prometheus gauges at /metrics)."""
    import threading

    from polyaxon_tpu.client.store import FileRunStore
    from polyaxon_tpu.scheduler import ControlPlane, ScheduleService, \
        make_server

    store = FileRunStore()
    srv = make_server(host, port, store,
                      plane=ControlPlane(store, auth_token=auth_token))
    if schedules:
        service = ScheduleService(store)
        threading.Thread(target=service.run_forever, daemon=True).start()
    click.echo(f"control plane on http://{host}:{port} (home={store.home})")
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        srv.server_close()


@cli.command()
@click.option("--name", default="agent-0")
@click.option("--host", default=None,
              help="Control plane URL (default: POLYAXON_TPU_HOST, else "
                   "in-process over the local store).")
@click.option("--backend", type=click.Choice(["local", "manifest", "kube"]),
              default="local")
@click.option("--cluster-dir", default=None,
              help="Manifest backend: directory the operator watches.")
@click.option("--max-concurrent", default=8, type=int)
@click.option("--queue", "queues", multiple=True,
              help="Serve only these queues (repeatable; default: all).")
def agent(name, host, backend, cluster_dir, max_concurrent, queues):
    """Run an agent: claim queued runs and execute them."""
    from polyaxon_tpu.runner.agent import (Agent, KubeBackend, LocalBackend,
                                           ManifestBackend)
    from polyaxon_tpu.scheduler import ControlPlane

    host = host or os.environ.get("POLYAXON_TPU_HOST")
    if host:
        from polyaxon_tpu.client.api_client import ApiRunStore

        plane = ApiRunStore(host)
    else:
        plane = ControlPlane()

    if backend == "manifest":
        if not cluster_dir:
            raise click.ClickException(
                "--backend manifest requires --cluster-dir")
        be = ManifestBackend(cluster_dir)
    elif backend == "kube":
        # API server + token from PTPU_K8S_* env or in-cluster config.
        be = KubeBackend()
    else:
        store = getattr(plane, "store", plane)
        be = LocalBackend(store)
    worker = Agent(plane, backend=be, name=name,
                   max_concurrent=max_concurrent,
                   queues=list(queues) or None)
    click.echo(f"agent {name} polling "
               f"{host or 'local store'} (backend={backend})")
    try:
        worker.run_forever()
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    cli()
