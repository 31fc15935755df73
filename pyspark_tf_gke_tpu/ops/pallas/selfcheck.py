"""Main-path Pallas kernels against their pure-JAX references, COMPILED,
at the shapes the trainer and the slot-engine server run.

``python -m pyspark_tf_gke_tpu.ops.pallas.selfcheck`` is the kernel leg
of ``chip_smoke.py``: every kernel on the training and serving path runs
with ``interpret=False`` on the TPU, in bf16, at the GPT-2-small shapes
(12 heads x head_dim 64, hidden 768, S=1024, KV page 64) and must agree
with its reference within :data:`TOLERANCE`. A kernel Mosaic refuses is
reported with the compiler's message and fails the run — nothing here
substitutes a reference or an interpret-mode result on the chip.

``--tiny`` is the CPU rehearsal: toy shapes, kernels in interpret mode,
platform expected to be ``cpu``. Without it a non-TPU backend exits 3
before running anything. ``tools/smoke_check.py --kernels-only`` stays
the f32 interpret-mode functional sweep (ResNet kernels included); this
module is the chip gate.

Last stdout line: one JSON object (device, versions, per-kernel error).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Callable, Dict, Iterator, Tuple

# max |kernel - reference| / max(1, max |reference|), bf16 in, f32
# accumulation on both sides: bf16 keeps 8 mantissa bits (2^-8 = 4e-3
# per rounding) and each side rounds the softmax probabilities and the
# output once more, so a correct kernel lands within a few of those; a
# wrong mask, a dropped scale or a mis-ordered head is O(1).
TOLERANCE = 2e-2


def _shapes(tiny: bool) -> dict:
    if tiny:
        return dict(slots=4, heads=4, head_dim=16, page=8, max_pages=4,
                    pages=16, chunk=8, gqa_kv=2, train_b=2, seq=256,
                    hidden=64, window=dict(seq=256, heads=2, head_dim=16,
                                           window=128, block=64))
    # what chip_smoke.py's trainer and server legs run: lm_pretrain
    # defaults at --seq-len 1024 --batch-size 8; serve
    # --continuous-slots 8 --prefill-chunk 128 on a page-64 bundle with
    # slots x 16 pages
    return dict(slots=8, heads=12, head_dim=64, page=64, max_pages=16,
                pages=128, chunk=128, gqa_kv=4, train_b=8, seq=1024,
                hidden=768,
                # a window layer of the hybrid decoder at its cell's shape
                # (models/hybrid_lm.py::GatedAttention): heads of 128, the
                # default 512-row blocks, four blocks to a window
                window=dict(seq=8192, heads=4, head_dim=128, window=2048,
                            block=None))


def _cases(tiny: bool, interpret: bool
           ) -> Iterator[Tuple[str, Callable[[], Tuple]]]:
    """(name, thunk) pairs; each thunk returns ``(got, want)`` pytrees."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pyspark_tf_gke_tpu.ops.attention import dot_product_attention
    from pyspark_tf_gke_tpu.ops.pallas.flash_attention import flash_attention
    from pyspark_tf_gke_tpu.ops.pallas.layernorm import fused_layernorm
    from pyspark_tf_gke_tpu.ops.pallas.paged_attention import (
        paged_attention,
        paged_attention_chunk,
        paged_attention_chunk_reference,
        paged_attention_reference,
    )

    sh = _shapes(tiny)
    rng = np.random.default_rng(0)
    bf16 = jnp.bfloat16
    h, d, p_sz, mp, n = (sh["heads"], sh["head_dim"], sh["page"],
                         sh["max_pages"], sh["pages"])
    b, s_c = sh["slots"], sh["chunk"]

    def normal(shape, dtype=bf16):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    # block table: distinct pages per slot, one slot with unallocated
    # (sentinel = n) tail entries; fills ragged — a full slot, a
    # mid-page one, an empty one
    table = rng.permutation(n)[:b * mp].reshape(b, mp).astype(np.int32)
    table[1, 2:] = n
    fills = rng.integers(1, mp * p_sz + 1, (b,)).astype(np.int32)
    fills[0], fills[1], fills[2] = mp * p_sz, p_sz + 3, 0
    table, fills = jnp.asarray(table), jnp.asarray(fills)
    # chunk rows: a prompt's first piece (fill == S) and a later piece
    # ending mid-page; fills include the chunk's own S tokens
    table_c = table[2:4]
    fills_c = jnp.asarray([s_c, 2 * p_sz + s_c - 1], jnp.int32)

    def paged(hkv: int, quant: bool):
        if quant:
            kp, vp = (jnp.asarray(
                rng.integers(-127, 128, (n, p_sz, hkv, d)), jnp.int8)
                for _ in range(2))
            scales = dict(
                k_scales=jnp.asarray(
                    rng.random((n, p_sz, hkv)) * 0.02 + 1e-3, jnp.float32),
                v_scales=jnp.asarray(
                    rng.random((n, p_sz, hkv)) * 0.02 + 1e-3, jnp.float32))
        else:
            kp, vp = normal((n, p_sz, hkv, d)), normal((n, p_sz, hkv, d))
            scales = {}
        q1 = normal((b, h, d))
        qc = normal((2, s_c, h, d))

        def decode():
            return (paged_attention(q1, kp, vp, table, fills,
                                    interpret=interpret, **scales),
                    paged_attention_reference(q1, kp, vp, table, fills,
                                              **scales))

        def chunk():
            return (paged_attention_chunk(qc, kp, vp, table_c, fills_c,
                                          interpret=interpret, **scales),
                    paged_attention_chunk_reference(
                        qc, kp, vp, table_c, fills_c, **scales))

        return decode, chunk

    for tag, hkv, quant in (("mha", h, False), ("gqa", sh["gqa_kv"], False),
                            ("int8", h, True)):
        decode, chunk = paged(hkv, quant)
        yield f"paged_attention[{tag}]", decode
        yield f"paged_attention_chunk[{tag},S={s_c}]", chunk

    tb, s = sh["train_b"], sh["seq"]
    q, k, v, g = (normal((tb, s, h, d)) for _ in range(4))

    def flash_fwd():
        return (flash_attention(q, k, v, causal=True, interpret=interpret),
                dot_product_attention(q, k, v, causal=True))

    def flash_bwd():
        def grads(fn):
            return jax.grad(
                lambda q_, k_, v_: jnp.sum(
                    fn(q_, k_, v_).astype(jnp.float32)
                    * g.astype(jnp.float32)),
                argnums=(0, 1, 2))(q, k, v)

        return (grads(lambda *a: flash_attention(
                    *a, causal=True, interpret=interpret)),
                grads(lambda *a: dot_product_attention(*a, causal=True)))

    yield f"flash_attention[causal,fwd,S={s}]", flash_fwd
    yield f"flash_attention[causal,bwd,S={s}]", flash_bwd

    win = sh["window"]
    wq, wk, wv, wg = (normal((1, win["seq"], win["heads"], win["head_dim"]))
                      for _ in range(4))
    windowed = dict(causal=True, window=win["window"])

    def window_flash():
        def both(fn):
            out, pull = jax.vjp(fn, wq, wk, wv)
            return out, pull(wg)

        return (both(lambda *a: flash_attention(
                    *a, block_q=win["block"], block_k=win["block"],
                    interpret=interpret, **windowed)),
                both(lambda *a: dot_product_attention(*a, **windowed)))

    yield (f"flash_attention[window={win['window']},fwd+bwd,S={win['seq']}]",
           window_flash)

    hid = sh["hidden"]
    x, r = normal((tb * s, hid)), normal((tb * s, hid))
    scale = jnp.asarray(rng.standard_normal(hid), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(hid), jnp.float32)

    def ln_ref(xx):
        xf = xx.astype(jnp.float32)
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        return ((xf - mu) * jax.lax.rsqrt(var + 1e-5) * scale
                + bias).astype(bf16)

    yield f"fused_layernorm[{tb * s}x{hid}]", lambda: (
        fused_layernorm(x, scale, bias, eps=1e-5, interpret=interpret),
        ln_ref(x))
    yield f"fused_layernorm[residual,{tb * s}x{hid}]", lambda: (
        fused_layernorm(x, scale, bias, eps=1e-5, interpret=interpret,
                        residual=r),
        ln_ref(x.astype(jnp.float32) + r.astype(jnp.float32)))


def _rel_err(got, want) -> float:
    import jax
    import numpy as np

    worst = 0.0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        if a.shape != b.shape:
            raise ValueError(f"shape {a.shape} != reference {b.shape}")
        if not np.all(np.isfinite(a)):
            return float("inf")
        worst = max(worst, float(np.max(np.abs(a - b))
                                 / max(1.0, np.max(np.abs(b)))))
    return worst


def run(tiny: bool) -> Tuple[dict, int]:
    import importlib.metadata as md

    import jax

    from pyspark_tf_gke_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    report: Dict = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "versions": {pkg: md.version(pkg)
                     for pkg in ("jax", "jaxlib", "libtpu")},
        "tiny": tiny,
        "interpret": tiny,
        "tolerance": TOLERANCE,
        "compile_cache": cache_dir,
        "kernels": {},
    }
    want_platform = "cpu" if tiny else "tpu"
    if dev.platform != want_platform:
        print(f"selfcheck: needs platform {want_platform!r}, JAX found "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return report, 3
    failed = 0
    for name, thunk in _cases(tiny, interpret=tiny):
        try:
            got, want = thunk()
            jax.block_until_ready(got)
            err = _rel_err(got, want)
            ok = err <= TOLERANCE
            report["kernels"][name] = {"ok": ok, "err": round(err, 5)}
        except Exception as exc:  # noqa: BLE001 — reported per kernel
            ok = False
            # Mosaic's message leads; tracebacks go to stderr
            import traceback

            traceback.print_exc()
            report["kernels"][name] = {
                "ok": False,
                "error": f"{type(exc).__name__}: {exc}"[:600]}
        print(f"kernel {name}: {report['kernels'][name]}", file=sys.stderr,
              flush=True)
        failed += 0 if ok else 1
    return report, 1 if failed else 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--tiny", action="store_true",
                   help="CPU rehearsal: toy shapes, interpret mode")
    args = p.parse_args(argv)
    report, rc = run(args.tiny)
    if rc != 3:
        print(json.dumps(report, separators=(",", ":")))
    return rc


if __name__ == "__main__":
    sys.exit(main())
