#!/usr/bin/env python3
"""Chip smoke test: serve minitron-4b at full width on one TPU.

    python chip_smoke.py               # one chip: seda vs off
    python chip_smoke.py --four-chips  # four chips: 4-shard cluster only

The one-chip run drives the serving CLI (``repro.launch.serve.main``)
with ``--engine paged`` at the published ``minitron-4b`` widths and
random bf16 weights from ``--seed``: 8 requests with prompts of 128 to
512 tokens, 32 generated tokens each, 16-token pages.  It first checks
every Pallas kernel of the page path bit for bit against its jnp
oracle on the chip, then serves the same requests under ``seda`` and
under ``off`` (one copy of the weights at a time) and fails unless

* the greedy tokens under ``seda`` equal those under ``off``,
* the fused read and write kernels carried the ``seda`` ticks,
* the compiled ``seda`` decode step contains a ``tpu_custom_call``,
* both deferred pool-MAC checks pass.

``--four-chips`` runs only the sharded path: the same requests through
a single engine and through a 4-shard cluster engine with one shard
per chip, which must hold 4 distinct devices and reproduce the
single-engine tokens.

The last line of standard output is one JSON object naming the device;
it is printed only when every check passed.  Without a TPU the script
exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ARCH = "minitron-4b"
# Mixed 128..512-token prompts; four distinct lengths keep the number of
# compiled prefill and page-write programs small.
PROMPT_LENS = (512, 128, 384, 256, 512, 128, 384, 256)
GEN_LEN = 32
PAGE_TOKENS = 16
# The 4-chip phase compiles every program once per chip: one prompt
# length and fewer generated tokens keep it short.
CLUSTER_PROMPT_LENS = (256,) * 8
CLUSTER_GEN_LEN = 8


class SmokeFailure(RuntimeError):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"[smoke] ok: {what}", flush=True)


def _serve(serve, scheme: str, seed: int, prompt_lens=PROMPT_LENS,
           gen_len: int = GEN_LEN, shards: int = 0):
    """One run of the serving CLI: (engine, tokens, deferred MAC ok).

    Its tick-phase trace (which also feeds the tick-latency histogram)
    goes to a scratch directory.  Only the engine refers to the
    weights, so dropping it releases them."""
    args = ["--arch", ARCH, "--engine", "paged", "--scheme", scheme,
            "--batch", str(len(prompt_lens)),
            "--prompt-len", ",".join(map(str, prompt_lens)),
            "--gen-len", str(gen_len), "--page-tokens", str(PAGE_TOKENS),
            "--seed", str(seed)]
    if shards:
        args += ["--shards", str(shards)]
    with tempfile.TemporaryDirectory() as tmp:
        out = serve.main(args + ["--trace-out",
                                 os.path.join(tmp, "trace.json")])
    return out["engine"], np.asarray(out["tokens"]), out["deferred_mac_ok"]


def _release(what: str) -> None:
    """Drop the finished run's engine and check its weights left the
    device before the next run loads its own copy."""
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    _check(live < 1 << 30, f"{what} released: {live} bytes of arrays "
           f"still live")


def _check_kernels(seed: int) -> None:
    """Every kernel of the page path, bit-identical to its oracle."""
    import jax.numpy as jnp

    from repro.kernels.aes_ctr import kernel as aes_k
    from repro.kernels.aes_ctr.ref import aes_ctr_keystream_lanes_ref
    from repro.kernels.fused_crypt_mac import kernel as fk
    from repro.kernels.fused_crypt_mac import ref as fr

    rng = np.random.default_rng(seed)
    n, s = 3000, 4                           # seda: 64-byte blocks
    lanes = 4 * s

    def u32(*shape):
        return jnp.asarray(rng.integers(0, 2**32, shape, dtype=np.uint32))

    def same(got, want, what):
        _check(all(np.array_equal(np.asarray(g), np.asarray(w))
                   for g, w in zip(jax.tree.leaves(got),
                                   jax.tree.leaves(want))),
               f"{what} kernel bit-identical to its oracle on the chip")

    cw = u32(n, 4)
    rk = jnp.asarray(rng.integers(0, 256, (11, 16), dtype=np.uint8))
    rk_per = jnp.asarray(rng.integers(0, 256, (n, 11, 16), dtype=np.uint8))
    same(aes_k.aes_ctr_keystream(cw, rk),
         aes_ctr_keystream_lanes_ref(cw, rk), "aes_ctr_keystream")
    same(aes_k.aes_ctr_keystream_multi(cw, rk_per),
         jax.vmap(lambda c, r: aes_ctr_keystream_lanes_ref(c[None], r)[0])(
             cw, rk_per), "aes_ctr_keystream_multi")
    data, base, bind = u32(n, lanes), u32(n, 4), u32(n, 8)
    single = (data, base, u32(s, 4), bind, u32(lanes + 8))
    mixed = (data, base, u32(n, s, 4), bind, u32(n, lanes + 8))
    for name, args in (("fused_crypt_mac", single),
                       ("fused_crypt_mac_write", single),
                       ("fused_crypt_mac_mixed", mixed),
                       ("fused_crypt_mac_write_mixed", mixed)):
        same(getattr(fk, name)(*args), getattr(fr, name + "_ref")(*args),
             name)


def _decode_hlo(eng) -> str:
    """Compiled HLO of the engine's widest decode variant."""
    bucket, uniform = max(eng._decode_fns)
    fn = eng._decode_fn_for(bucket, uniform)
    return fn.lower(*eng._decode_analysis_args(bucket)).compile().as_text()


def _steady_tok_s(eng) -> float:
    """Requests over the median tick (every request decodes in every
    tick of this workload): one unchecked run."""
    ticks = eng.metrics.histograms["tick_seconds"]
    return eng.max_slots / ticks.percentile(50)


def one_chip(serve, seed: int) -> None:
    _check_kernels(seed)
    runs = {}
    for scheme in ("seda", "off"):
        t0 = time.perf_counter()
        eng, tokens, mac_ok = _serve(serve, scheme, seed)
        wall = time.perf_counter() - t0
        cfg = eng.cfg
        print(f"[smoke] {scheme}: config {cfg.name} (layers={cfg.n_layers} "
              f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv} "
              f"d_ff={cfg.d_ff} vocab={cfg.vocab} dtype={cfg.dtype}), "
              f"wall {wall:.1f} s incl. compile, steady decode "
              f"~{_steady_tok_s(eng):.1f} tok/s (one unchecked run)",
              flush=True)
        runs[scheme] = dict(
            tokens=tokens, mac_ok=mac_ok,
            read_fused=eng.stats["fused_read_ticks"],
            write_fused=eng.stats["fused_write_ticks"],
            custom_call=(scheme == "seda"
                         and "tpu_custom_call" in _decode_hlo(eng)))
        del eng
        _release(f"{scheme} run")
    seda, off = runs["seda"], runs["off"]
    _check(np.array_equal(seda["tokens"], off["tokens"]),
           "greedy tokens under seda equal those under off")
    _check(seda["read_fused"] > 0 and seda["write_fused"] > 0,
           f"fused kernels carried the seda ticks (read "
           f"{seda['read_fused']}, write {seda['write_fused']})")
    _check(seda["custom_call"], "compiled seda decode contains "
           "tpu_custom_call")
    _check(seda["mac_ok"] and off["mac_ok"], "deferred pool-MAC checks pass")


def four_chips(serve, seed: int) -> None:
    _check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices >= 4")
    kw = dict(prompt_lens=CLUSTER_PROMPT_LENS, gen_len=CLUSTER_GEN_LEN)
    eng, single, single_ok = _serve(serve, "seda", seed, **kw)
    del eng
    _release("single-engine run")
    cluster, tokens, cluster_ok = _serve(serve, "seda", seed, shards=4, **kw)
    devices = [e._device for e in cluster.engines]
    _check(len({d.id for d in devices if d is not None}) == 4,
           f"4 shard engines hold 4 distinct devices "
           f"({[str(d) for d in devices]})")
    for e, d in zip(cluster.engines, devices):
        _check(all(leaf.devices() == {d}
                   for leaf in jax.tree.leaves(e.params)),
               f"shard {e.shard_id} weights live on {d}")
    _check(np.array_equal(tokens, single),
           "4-shard cluster tokens equal the single-engine tokens")
    _check(cluster_ok and single_ok, "deferred root and pool MAC checks pass")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-shard cluster phase (4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"[smoke] FAIL: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[smoke] FAIL: no TPU (JAX sees {devices[0].platform})",
              file=sys.stderr)
        return 1

    from repro.launch import serve
    cache_dir = serve.enable_compile_cache()
    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compile_s.append(secs)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    dev = devices[0]
    print(f"[smoke] device {dev.platform} {dev.device_kind} x{len(devices)}, "
          f"compile cache {cache_dir}", flush=True)
    try:
        if args.four_chips:
            four_chips(serve, args.seed)
        else:
            one_chip(serve, args.seed)
    except SmokeFailure as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        return 1
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    print(f"[smoke] backend compile {sum(compile_s):.1f} s over "
          f"{len(compile_s)} programs; peak device memory {peak} bytes",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
