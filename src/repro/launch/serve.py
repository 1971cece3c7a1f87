"""Serving launcher: LM prefill+decode loop, or the HDC streaming fleet.

LM zoo (reduced config, CPU):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --prompt-len 32 --gen 16

HDC streaming fleet (population-scale seizure detection):
  PYTHONPATH=src python -m repro.launch.serve --hdc-fleet \
      --sessions 256 --patients 8 --rounds 4

Deploy flow — compile once, serve many (runtime/aot.py): `compile` writes a
versioned artifact of serialized pre-compiled step executables; every later
`serve --aot-dir` warms the fleet from it and the first decision costs
milliseconds of deserialization instead of seconds of trace+compile (a
stale artifact — different jax version / device kind / kernel sources —
falls back to JIT with a warning):
  PYTHONPATH=src python -m repro.launch.serve compile --aot-dir /tmp/aot \
      --sessions 256 --patients 8
  PYTHONPATH=src python -m repro.launch.serve --hdc-fleet --aot-dir /tmp/aot \
      --sessions 256 --patients 8 --rounds 4

Durable adaptive fleet: --adapt-every N personalizes every session's AM via
one jitted fleet-wide online update each N rounds; --ckpt-dir saves the full
fleet state (streaming accumulators + online AM banks) after the run and
--resume restores the latest checkpoint to continue mid-stream bit-exactly:

  PYTHONPATH=src python -m repro.launch.serve --hdc-fleet \
      --sessions 256 --patients 8 --rounds 8 --adapt-every 2 \
      --ckpt-dir /tmp/fleet-ckpt --resume

Channel-fault tolerance: --channel-health builds the fleet with per-session
channel masking and runs the online electrode-health monitor
(reliability/channels.py) over every round's LBP codes — channels whose code
statistics collapse (dead/railed/line-noise electrodes) are quarantined out
of the spatial encoder via a traced-operand mask update (zero recompiles)
and reinstated with hysteresis if they recover; the quarantine event log is
printed at the end of the run.  --inject-fault CH:KIND demos it by faulting
a channel of every session's stream:

  PYTHONPATH=src python -m repro.launch.serve --hdc-fleet \
      --sessions 64 --patients 4 --rounds 8 --channel-health \
      --inject-fault 3:dead --inject-fault 7:line_noise

On a fleet the same entry points run on the production mesh (--mesh 16x16):
the LM path shards the KV cache per runtime/sharding.py, the HDC path shards
the per-session accumulator state along the data axis (serve/fleet.py) while
the codebook/AM banks replicate.
"""

from __future__ import annotations

import argparse
import signal
import time

import jax
import jax.numpy as jnp

from repro.launch.mesh import parse_mesh


class _GracefulStop:
    """SIGTERM/SIGINT -> finish the in-flight round, write one final atomic
    checkpoint, exit 0.  The flag is only *read* at round boundaries, so a
    kill mid-push never tears the fleet state — the checkpoint the next
    worker resumes from is always a complete round (ckpt saves are already
    atomic: tmp dir + rename)."""

    def __init__(self):
        self.signum: int | None = None
        self._old: dict[int, object] = {}

    def __enter__(self):
        for sig in (signal.SIGTERM, signal.SIGINT):
            self._old[sig] = signal.signal(sig, self._handle)
        return self

    def __exit__(self, *exc):
        for sig, old in self._old.items():
            signal.signal(sig, old)
        return False

    def _handle(self, signum, frame):
        if self.signum is not None:  # second signal: give up immediately
            raise KeyboardInterrupt
        self.signum = signum

    @property
    def requested(self) -> bool:
        return self.signum is not None

    @property
    def name(self) -> str:
        return signal.Signals(self.signum).name if self.signum else ""


def train_bank(cfg, patients: int, rng) -> dict:
    """Train a synthetic per-patient bank: ``patient<p>`` -> pipeline, each
    calibrated to its own density operating point (the programmed temporal
    threshold register) and trained one-shot on random codes from ``rng``
    (a ``numpy.random.Generator``)."""
    import numpy as np

    from repro.core.pipeline import HDCPipeline

    def trained(seed: int) -> HDCPipeline:
        codes = jnp.asarray(
            rng.integers(0, cfg.codes, (1, 4 * cfg.window, cfg.channels), np.uint8))
        labels = np.asarray(rng.integers(0, 2, (1, 4), np.int32))
        labels[0, :2] = (0, 1)  # every class needs >= 1 example (empty-class guard)
        pipe = HDCPipeline.init(jax.random.PRNGKey(seed), cfg)
        # per-patient calibrated operating point (the programmed register)
        pipe = pipe.calibrate_density(codes, target=0.2 + 0.05 * (seed % 4))
        return pipe.train_one_shot(codes, jnp.asarray(labels))

    return {f"patient{p}": trained(p) for p in range(patients)}


def _build_hdc_fleet(args):
    """Train a small synthetic per-patient bank and assemble the fleet."""
    import numpy as np

    from repro.core.pipeline import HDCConfig
    from repro.serve.fleet import StreamingFleet

    mesh = parse_mesh(args.mesh)
    cfg = HDCConfig(variant=args.variant)
    rng = np.random.default_rng(0)

    t0 = time.perf_counter()
    bank = train_bank(cfg, args.patients, rng)
    owners = [f"patient{i % args.patients}" for i in range(args.sessions)]
    fleet = StreamingFleet(bank, owners, mesh=mesh,
                           channel_masking=args.channel_health)
    print(f"fleet: {args.sessions} sessions over {args.patients} patients "
          f"({'mesh ' + 'x'.join(map(str, mesh.devices.shape)) if mesh else 'single device'}), "
          f"built in {time.perf_counter() - t0:.1f} s")
    return fleet, cfg, rng, mesh


def run_hdc_compile(args) -> None:
    """``compile`` subcommand: serialize + pre-compile the fleet's whole
    executable set into the --aot-dir deploy artifact (runtime/aot.py), so
    ``serve --aot-dir <dir>`` workers start without paying trace+compile."""
    if not args.aot_dir:
        raise SystemExit("compile mode needs --aot-dir <artifact directory>")
    fleet, _, _, mesh = _build_hdc_fleet(args)
    if mesh is not None:
        raise SystemExit("compile mode serializes single-device executables; "
                         "drop --mesh")
    t0 = time.perf_counter()
    manifest = fleet.save_aot(args.aot_dir)
    dt = time.perf_counter() - t0
    print(f"AOT artifact -> {args.aot_dir}: {len(manifest['entries'])} "
          f"executables in {dt:.1f} s (key: {manifest['key']})")
    for e in manifest["entries"]:
        print(f"  {e['name']}  exported={e['exported']} "
              f"compile={e['compile_s']:.2f}s")


def run_hdc_fleet(args) -> None:
    """Stream a (possibly sharded) fleet; --aot-dir warms it from a deploy
    artifact first."""
    import numpy as np

    fleet, cfg, rng, _ = _build_hdc_fleet(args)

    t0 = time.perf_counter()
    if args.aot_dir:
        from repro.runtime import aot as aot_mod

        art = aot_mod.load_artifact(args.aot_dir)  # None (+warning) if stale
        stats = fleet.warmup(aot=art)
        print(f"warmup from {args.aot_dir}: {stats['loaded']} loaded, "
              f"{stats['compiled']} compiled in "
              f"{time.perf_counter() - t0:.2f} s"
              + ("" if art is not None else "  [stale artifact: JIT]"))

    chunk_len = args.chunk or cfg.window
    chunks = [rng.integers(0, cfg.codes, (chunk_len, cfg.channels), np.uint8)
              for _ in range(args.sessions)]
    if args.inject_fault:
        from repro.reliability import channels as chan_mod

        frng = np.random.default_rng(1)
        for spec in args.inject_fault:
            ch_s, _, kind = spec.partition(":")
            try:
                ch = int(ch_s)
            except ValueError:
                raise SystemExit(f"--inject-fault {spec!r}: want CH:KIND")
            if kind not in chan_mod.CODE_FAULT_TYPES:
                raise SystemExit(
                    f"--inject-fault kind {kind!r} must be one of "
                    f"{chan_mod.CODE_FAULT_TYPES}")
            if not 0 <= ch < cfg.channels:
                raise SystemExit(
                    f"--inject-fault channel {ch} outside "
                    f"[0, {cfg.channels})")
            chunks = [chan_mod.inject_code_fault(c, ch, kind, frng)
                      for c in chunks]
            print(f"injected {kind} fault on channel {ch} "
                  f"(all {args.sessions} sessions)")
    monitor = None
    if args.channel_health:
        from repro.reliability.channels import FleetChannelMonitor

        monitor = FleetChannelMonitor(args.sessions, cfg.channels)
    fleet.push(chunks)  # warmup / compile (no-op compile when AOT-warmed)

    # restore AFTER the warmup push: restore overwrites the fleet state, so
    # the warmup round never leaks into the resumed stream (which would
    # silently advance it by one chunk per resume)
    if args.resume and args.ckpt_dir:
        from repro.ckpt import checkpoint as ckpt
        if ckpt.latest_step(args.ckpt_dir) is not None:
            step = fleet.restore(args.ckpt_dir)
            print(f"resumed fleet from {args.ckpt_dir} step {step} "
                  f"(frames so far: {int(fleet.frame_indices.sum())})")
        else:
            print(f"--resume: no checkpoint under {args.ckpt_dir}, cold start")
    decisions = 0
    adapted = 0
    rounds_done = 0
    t0 = time.perf_counter()
    with _GracefulStop() as stopper:
        for r in range(args.rounds):
            if stopper.requested:
                break
            out = fleet.push(chunks)
            decisions += sum(len(o) for o in out)
            rounds_done = r + 1
            if monitor is not None:
                masks = monitor.observe(np.stack(chunks))
                if not np.array_equal(masks, fleet.channel_masks):
                    fleet.set_channel_mask(masks)
            if args.adapt_every and (r + 1) % args.adapt_every == 0:
                # synthetic feedback: label each session's last frame at random
                labels = np.where([len(o) > 0 for o in out],
                                  rng.integers(0, cfg.n_classes, args.sessions),
                                  -1)
                adapted += int(fleet.adapt(labels).sum())
            if (args.ckpt_dir and args.ckpt_every
                    and (r + 1) % args.ckpt_every == 0):
                fleet.save(args.ckpt_dir)
    dt = time.perf_counter() - t0
    rate = args.sessions * rounds_done / max(dt, 1e-9)
    print(f"stream: {rounds_done} rounds x {chunk_len} cycles in {dt * 1e3:.1f} ms "
          f"({rate:.0f} session-chunks/s, {decisions} decisions, "
          f"{dt * 1e6 / max(decisions, 1):.1f} us/decision)")
    if args.adapt_every:
        print(f"online adaptation: {adapted} gated AM updates across the fleet")
    if monitor is not None:
        ev = monitor.events
        print(f"channel health: {monitor.n_quarantined} channel(s) "
              f"quarantined across the fleet ({len(ev)} events)")
        for e in ev[:20]:
            print(f"  round {e['block']} session {e['session']} "
                  f"ch {e['channel']}: {e['event']} "
                  f"(entropy {e['entropy']:.2f} bits, "
                  f"run {e['stuck_run']})")
        if len(ev) > 20:
            print(f"  ... {len(ev) - 20} more event(s)")
    print(f"compiled step executables: {fleet.compile_count} "
          f"(buckets: {fleet._buckets})")
    if args.ckpt_dir:
        path = fleet.save(args.ckpt_dir)
        print(f"saved fleet checkpoint -> {path}")
    if stopper.requested:
        # the final atomic checkpoint above IS the shutdown contract; exit
        # clean so supervisors treat this as a graceful drain, not a crash
        print(f"caught {stopper.name}: checkpointed after round "
              f"{rounds_done}, exiting 0")
        raise SystemExit(0)


def run_lm(args) -> None:
    from repro.configs.registry import get_config
    from repro.data import lm as lmdata
    from repro.models import params as P
    from repro.models import serve as S
    from repro.runtime import steps as steps_mod
    from repro.runtime.sharding import make_ctx, tree_shardings

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = parse_mesh(args.mesh)
    cache_seq = args.prompt_len + args.gen
    shape = lmdata.ShapeSpec("serve", args.prompt_len, args.batch, "prefill")
    batch = lmdata.synth_batch(jax.random.PRNGKey(0), cfg, shape)
    specs = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)

    prefill_fn, ctx, spec = steps_mod.jit_prefill(
        cfg, mesh, specs, cache_seq, seq_sharded_kv=args.seq_sharded_kv)
    params = P.initialize(jax.random.PRNGKey(1), spec, jnp.dtype(cfg.dtype))
    if mesh is not None:
        shardings = tree_shardings(spec, ctx)
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, s) if s is not None else x,
            params, shardings)

    t0 = time.perf_counter()
    logits, caches = prefill_fn(params, batch)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0
    print(f"prefill: {args.batch} x {args.prompt_len} tokens in "
          f"{t_prefill * 1e3:.1f} ms")

    def decode(p, t, c, q):
        return S.decode_step(p, t, c, q, cfg,
                             make_ctx(mesh, seq_sharded_kv=args.seq_sharded_kv))

    decode_fn = jax.jit(decode)
    n_media = cfg.num_media_tokens if cfg.family == "vlm" else 0
    pos0 = batch["tokens"].shape[1] + n_media
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.perf_counter()
    for i in range(args.gen - 1):
        logits, caches = decode_fn(params, tok, caches,
                                   jnp.asarray(pos0 + i, jnp.int32))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_dec = time.perf_counter() - t0
    gen = jnp.concatenate(out_tokens, axis=1)
    print(f"decode: {args.gen - 1} steps in {t_dec * 1e3:.1f} ms "
          f"({(args.gen - 1) * args.batch / max(t_dec, 1e-9):.1f} tok/s)")
    print("generated token ids (greedy):")
    for b in range(min(args.batch, 4)):
        print(f"  [{b}] {gen[b].tolist()}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("command", nargs="?", default="serve",
                    choices=["serve", "compile"],
                    help="serve (default) or compile: build the --aot-dir "
                         "deploy artifact for the HDC fleet and exit")
    ap.add_argument("--arch", default=None, help="LM zoo architecture to serve")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--seq-sharded-kv", action="store_true")
    # HDC streaming-fleet mode
    ap.add_argument("--hdc-fleet", action="store_true",
                    help="serve the HDC seizure-detection streaming fleet")
    ap.add_argument("--sessions", type=int, default=64)
    ap.add_argument("--patients", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=None,
                    help="cycles per session per round (default: one window)")
    ap.add_argument("--variant", default="sparse_compim",
                    choices=["sparse_naive", "sparse_compim", "dense"])
    ap.add_argument("--channel-health", action="store_true",
                    help="build the fleet with channel masking and run the "
                         "online electrode-health monitor: channels whose "
                         "LBP code statistics collapse are quarantined out "
                         "of the spatial encoder (traced mask update, no "
                         "recompiles) and reinstated with hysteresis")
    ap.add_argument("--inject-fault", action="append", default=[],
                    metavar="CH:KIND",
                    help="inject a code-level electrode fault into channel "
                         "CH of every session's stream (KIND: dead, "
                         "saturated, line_noise, dropout); repeatable")
    ap.add_argument("--adapt-every", type=int, default=0,
                    help="run one fleet-wide online AM update every N rounds")
    ap.add_argument("--ckpt-dir", default=None,
                    help="save the fleet state here after the run")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="with --ckpt-dir: also checkpoint every N rounds "
                         "(periodic crash-recovery saves, not just the "
                         "final one)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint from --ckpt-dir "
                         "before streaming")
    ap.add_argument("--aot-dir", default=None,
                    help="deploy-artifact directory of serialized executables"
                         " (runtime/aot.py): `compile` writes it, `serve` "
                         "warms the fleet from it")
    args = ap.parse_args()
    from repro.runtime.aot import setup_compilation_cache

    setup_compilation_cache()
    if args.command == "compile":
        run_hdc_compile(args)
        return
    if args.hdc_fleet:
        run_hdc_fleet(args)
        return
    if not args.arch:
        ap.error("--arch is required (or pass --hdc-fleet)")
    run_lm(args)


if __name__ == "__main__":
    main()
