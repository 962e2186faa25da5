"""DeepSeek-V2's loss in the system against the plain reference at the
published widths, on the chip, once, outside any window (``PERF.md`` section 6
has the numbers).

    chiprun -- python3 perfbench/tests/reference_on_chip_deepseek_v2.py --seed N [--break KIND ...] [--layers N] [--platform cpu --tiny]

``deepseek-v2-ep16`` as the cell runs it (the dense layer and the four sparse
ones; the 10 experts, the 8 heads and the vocabulary slice held; ``--layers``
for fewer), seeded weights by the architecture's own rule, one batch of
``seq_len`` tokens. The same two losses
as ``reference_on_chip.py``, system against reference (float32, ``highest``
precision, attention in blocks of queries so that it fits):

``shifted``  the training loss: each position's target is the next token. With
             random weights and random targets this is ``log(rows) + var/2`` of
             the logits whatever the layers compute, so it is blind to them.
``greedy``   the same model on the reference's own most likely next tokens.
             A hidden state that turns away from the reference's loses the
             largest logit, so this one sees every part of every layer. Its gap
             is given twice: of the means, and as the mean over positions of
             the absolute gap (``greedy_by_position``), where nothing cancels.

The reference is computed once; the sound system and every ``--break`` kind
asked for are compared with it in turn, a line each. A kind runs the system
with a part taken out (``shared_experts``; ``group_limit``: a plain top-6 over
all 160 experts; ``k_pe_rotation``: the shared rotary key left unrotated;
``kv_a_layernorm``) or with its weights rounded through float8_e4m3fn, the
nearest precision below the bf16 the configuration states (``fp8``): each has
to fall outside the tolerance that the sound system meets.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# Relative, on each gap: bf16 weights and activations against float32, all five
# layers. Between the largest gap the sound system read over three seeds
# (4.5e-3, by position) and the smallest a faulty variant gave (0.026 by
# position, 0.0119 of the means: the plain top-6 in place of the group-limited
# one; float8 weights 0.054), at their geometric mean, 0.0108 (PERF.md section
# 6, PR 32). At two layers the plain top-6 read 8.4e-3 beside a sound 2.4e-3:
# the comparison needs the depth the cell runs.
TOLERANCE = 1e-2
KINDS = ("shared_experts", "group_limit", "k_pe_rotation", "kv_a_layernorm", "fp8")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--break", dest="kinds", choices=KINDS, nargs="*", default=[])
    parser.add_argument("--layers", type=int, help="the first so many layers (default: all the configuration has)")
    parser.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from perfbench import run, trainstate

    if jax.devices()[0].platform != args.platform:
        raise SystemExit(f"jax found {jax.devices()[0].platform!r}, not {args.platform!r}")
    arch = run.find_architecture(ROOT, "deepseek_v2")
    ref = run.load_module("pb_reference_deepseek_v2", os.path.join(ROOT, "perfbench", "models", "reference", "deepseek_v2.py"))
    cfg = run.load_json(ROOT, "perfbench", "configs", "deepseek-v2-ep16.json")
    if args.tiny:
        cfg.update(arch.TINY, job=dict(cfg["job"], seq_len=96))
    cfg = dict(cfg, num_hidden_layers=args.layers or cfg["num_hidden_layers"])
    job = trainstate.Job(arch, cfg, jax.devices()[:1])
    params = job.init_state(args.seed)["params"]
    tokens = job.make_batches(args.seed, 1)[0]
    inputs, shifted = tokens[:, :-1], tokens[:, 1:]
    held = arch.held_experts(cfg)
    block = None if args.tiny else arch.QUERY_BLOCK
    want_logits = jax.jit(lambda p: ref.logits(cfg, p, inputs, held, block))(params)
    greedy = jnp.argmax(want_logits, axis=-1)
    logp = jax.nn.log_softmax(want_logits, axis=-1)
    want_nll = {
        name: -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        for name, targets in (("shifted", shifted), ("greedy", greedy))
    }
    want = {name: float(jnp.mean(nll)) for name, nll in want_nll.items()}
    del want_logits, logp
    layer, attention = arch.expert_layer, arch.attention
    broken = {
        "shared_experts": ("expert_layer", lambda c, p, x: layer(c, p, x, shared=False)),
        "group_limit": ("expert_layer", lambda c, p, x: layer(c, p, x, group_limit=False)),
        "k_pe_rotation": ("attention", lambda c, p, x: attention(c, p, x, rotate_key=False)),
        "kv_a_layernorm": ("attention", lambda c, p, x: attention(c, p, x, norm_kv=False)),
    }
    for kind in [None] + args.kinds:
        arch.expert_layer, arch.attention, weights = layer, attention, params
        if kind == "fp8":
            weights = jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params)
        elif kind:
            setattr(arch, *broken[kind])
        nll = jax.jit(lambda p, targets: arch.token_nll(cfg, p, inputs, targets))
        got_nll = {"shifted": nll(weights, shifted), "greedy": nll(weights, greedy)}
        got = {name: float(jnp.mean(x)) for name, x in got_nll.items()}
        gaps = {name: abs(got[name] - want[name]) / abs(want[name]) for name in want}
        # Position by position, so that gaps of either sign do not cancel in the mean.
        gaps["greedy_by_position"] = float(jnp.mean(jnp.abs(got_nll["greedy"] - want_nll["greedy"]))) / abs(want["greedy"])
        print(json.dumps({
            "device": jax.devices()[0].device_kind, "seed": args.seed, "broken": kind,
            "layers": cfg["num_hidden_layers"], "tokens": list(inputs.shape), "system": got, "reference": want,
            "relative_gap": gaps, "tolerance": TOLERANCE, "inside": all(g <= TOLERANCE for g in gaps.values()),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
