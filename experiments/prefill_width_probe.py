"""Does a prompt's prefill depend on the width it is padded to?

  python experiments/prefill_width_probe.py [--seed 0] [--smoke]
      [--lengths 71 123 ...]

Builds smollm-360m (``--smoke``: its smoke config) with random weights from
``--seed``, draws one prompt per length from ``numpy.random.default_rng(
seed)``, and prefills each prompt left-padded to width 128 and to width 256
under each attention impl (Pallas on a TPU, interpret mode elsewhere, and
the jnp ``xla`` path).  The real tokens see the same tile content at the
same relative offsets at both widths, so logits and the K/V of the real
columns should be bitwise equal.  Prints, per prompt and impl, whether a
repeated width-128 call is bitwise equal, the logits' relative L2 and max
abs difference between the widths, and the first layer whose K/V differs
with its relative L2 difference.
"""
import argparse
import sys

import numpy as np

sys.path.insert(0, "src")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--lengths", type=int, nargs="+",
                    default=[71, 123, 96, 127, 100, 115])
    args = ap.parse_args()

    import jax
    from repro.configs import get_config, get_smoke
    from repro.models import build_model

    cfg = (get_smoke if args.smoke else get_config)("smollm-360m")
    params = jax.jit(lambda k: build_model(cfg).init(k)[0])(
        jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in args.lengths]
    pallas = ("pallas" if jax.default_backend() == "tpu"
              else "pallas_interpret")
    print(f"backend {jax.default_backend()} seed {args.seed} "
          f"layers {cfg.n_layers} d_model {cfg.d_model}")
    for impl in (pallas, "xla"):
        prefill = jax.jit(build_model(cfg.replace(attn_impl=impl)).prefill)
        for prompt in prompts:
            n, out = len(prompt), []
            for width in (128, 128, 256):
                toks = np.full((1, width), cfg.pad_id, np.int32)
                toks[0, width - n:] = prompt
                logits, cache = prefill(params, {
                    "tokens": toks, "lengths": np.array([n], np.int32)})
                out.append((np.asarray(logits, np.float32),
                            np.asarray(cache["k"][:, 0, width - n:],
                                       np.float32),
                            np.asarray(cache["v"][:, 0, width - n:],
                                       np.float32)))
            (l0, k0, _), (l1, k1, v1), (l2, k2, v2) = out
            repeat = np.array_equal(l0, l1) and np.array_equal(k0, k1)
            kv_diff = [i for i in range(cfg.n_layers)
                       if not (np.array_equal(k1[i], k2[i])
                               and np.array_equal(v1[i], v2[i]))]
            first = ""
            if kv_diff:
                i = kv_diff[0]
                first = (f" K rel_l2 {_rel(k2[i], k1[i]):.3e}, last layer "
                         f"K rel_l2 {_rel(k2[-1], k1[-1]):.3e}")
            print(f"{impl} n {n} prompt_head {prompt[:4].tolist()}: repeat "
                  f"{'bitwise' if repeat else 'DIFFERS'}; logits rel_l2 "
                  f"{_rel(l2, l1):.3e} max_abs {np.abs(l1 - l2).max():.3e} "
                  f"argmax {'same' if l1.argmax() == l2.argmax() else 'differs'}"
                  f"; K/V first differing layer "
                  f"{kv_diff[0] if kv_diff else None} "
                  f"({len(kv_diff)} of {cfg.n_layers} differ){first}")


def _rel(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


if __name__ == "__main__":
    main()
