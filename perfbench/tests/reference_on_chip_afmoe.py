"""Trinity-Large-Preview's loss in the system against the plain reference at
the published widths, on the chip, once, outside any window (``PERF.md``
section 6 has the numbers).

    chiprun -- python3 perfbench/tests/reference_on_chip_afmoe.py --seed N [--break KIND ...] [--layers N] [--platform cpu --tiny]

``trinity-large-preview-ep32`` as the cells run it (the dense layer and the
period of sparse ones, three of them window layers and one full; the 8 experts
and the vocabulary slice held; the cells' micro-batch and ``seq_len`` 8192;
``--layers`` for fewer), seeded weights by the architecture's own rule, one
batch. The same two losses as ``reference_on_chip.py``, system against
reference (float32, ``highest`` precision, one score matrix a layer under the
explicit mask, computed a block of query rows at a time so that it fits):

``shifted``  the training loss: each position's target is the next token. With
             random weights and random targets this is ``log(rows) + var/2`` of
             the logits whatever the layers compute, so it is blind to them.
``greedy``   the same model on the reference's own most likely next tokens.
             A hidden state that turns away from the reference's loses the
             largest logit, so this one sees every part of every layer. Its gap
             is given twice: of the means, and as the mean over positions of
             the absolute gap (``greedy_by_position``), where nothing cancels.

The reference is computed once; the sound system and every ``--break`` kind
asked for are compared with it in turn, a line each, **twice**: in the dtypes
the configuration states (bf16 weights and activations, float32 where the
tree says so), and with the system's weights cast to float32 and its products
at ``highest`` precision. The first comparison sees the precision and the
large faults, the second sees the mathematics with no rounding in its way (of
the pairs a token sends to its experts a thirty-second reach the 8 held here,
so a fault of the routing moves the loss less than bf16's rounding does). A
kind runs the system with a part changed (``BROKEN``) or with its weights
rounded through float8_e4m3fn, the nearest precision below the bf16 the
configuration states (``fp8``): each has to fall outside one of the two
tolerances, and the sound system inside both.
"""

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# Relative, on each gap, all five layers at 8192 tokens (PERF.md section 6, PR 42; four seeds of the sound system, one
# with every kind, three more with the three smallest). In the stated dtypes the sound system's largest gap is 1.26e-3
# to 1.39e-3 (by position); float8 weights read 2.53e-2 to 2.64e-2 and the smallest faulty kind this comparison can
# see, the full layer rotated, 6.8e-3 to 8.4e-3; 5e-3 lies between with room both ways (3.6 times over the sound
# system, a fifth of float8). The bias let into the weights (1.4e-3 to 1.7e-3) drowns in bf16's rounding there: a
# thirty-second of a token's pairs reach the held experts. In float32 nothing is rounded away: the sound system reads
# 1.38e-7 to 1.39e-7 and that smallest kind 2.7e-4 to 7.2e-4 (route_norm off 1.1e-2, the bias out of the choice
# 1.5e-2, every other kind over 4e-2); 1e-5 is 72 times the one and a twenty-seventh of the other.
TOLERANCE = {"stated": 5e-3, "float32": 1e-5}
# The function of the architecture to replace, and the control it is called with.
BROKEN = {
    "no_window": ("attention", {"window": None}),  # every layer a full layer (still rotated)
    "half_window": ("attention", {"window": "half"}),  # resolved against the configuration's window below
    "full_rotated": ("attention", {"rotate": True}),
    "window_unrotated": ("attention", {"rotate": False}),
    "output_gate": ("attention", {"output_gate": False}),
    "qk_norm": ("attention", {"qk_norm": False}),
    "post_norms": ("layer", {"post_norms": False}),
    "bias_out_of_the_choice": ("expert_layer", {"bias_in_choice": False}),
    "bias_in_the_weights": ("expert_layer", {"bias_in_weights": True}),
    "route_norm": ("expert_layer", {"route_norm": False}),
    "shared_expert": ("expert_layer", {"shared": False}),
    "embed_scale": ("embed", {"scale": False}),
}
KINDS = tuple(BROKEN) + ("fp8",)


def broken(arch, cfg, kind):
    """``arch``'s function that ``kind`` replaces, by name, with the control
    bound; the caller puts it in the module and takes it out again."""
    name, control = BROKEN[kind]
    if control.get("window") == "half":
        control = {"window": cfg["sliding_window"] // 2}
    sound = getattr(arch, name)
    return name, lambda *args, **kwargs: sound(*args, **kwargs, **control)


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
    arch = run.find_architecture(ROOT, "afmoe")
    ref = run.load_module("pb_reference_afmoe", os.path.join(ROOT, "perfbench", "models", "reference", "afmoe.py"))
    cfg = run.load_json(ROOT, "perfbench", "configs", "trinity-large-preview-ep32.json")
    if args.tiny:
        cfg.update(arch.TINY, job=dict(cfg["job"], seq_len=96))
    cfg = dict(cfg, num_hidden_layers=args.layers or cfg["num_hidden_layers"])
    job = trainstate.Job(arch, cfg, jax.devices()[:1])
    params = job.init_state(args.seed)["params"]
    tokens = job.make_batches(args.seed, 1)[0]
    inputs, shifted = tokens[:, :-1], tokens[:, 1:]
    held = arch.held_experts(cfg)
    block = None if args.tiny else 512
    want_logits = jax.jit(lambda p: ref.logits(cfg, p, inputs, held, block))(params)
    greedy = jnp.argmax(want_logits, axis=-1)
    logp = jax.nn.log_softmax(want_logits, axis=-1)
    want_nll = {
        name: -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        for name, targets in (("shifted", shifted), ("greedy", greedy))
    }
    want = {name: float(jnp.mean(nll)) for name, nll in want_nll.items()}
    del want_logits, logp
    for kind in [None] + args.kinds:
        weights, patched = params, None
        if kind == "fp8":
            weights = jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params)
        elif kind:
            name, function = broken(arch, cfg, kind)
            patched = (name, getattr(arch, name))
            setattr(arch, name, function)
        line = {"device": jax.devices()[0].device_kind, "seed": args.seed, "broken": kind,
                "layers": cfg["num_hidden_layers"], "tokens": list(inputs.shape), "reference": want, "tolerance": TOLERANCE}
        try:
            for precision, tolerance in TOLERANCE.items():
                exact = precision == "float32"
                with jax.default_matmul_precision("highest") if exact else contextlib.nullcontext():
                    # The cast is inside the program, so that no second copy of the weights has to be held whole.
                    cast = (lambda p: jax.tree.map(lambda a: a.astype(jnp.float32), p)) if exact else (lambda p: p)
                    nll = jax.jit(lambda p, targets: arch.token_nll(cfg, cast(p), inputs, targets))
                    got_nll = {"shifted": nll(weights, shifted), "greedy": nll(weights, greedy)}
                got = {name: float(jnp.mean(x)) for name, x in got_nll.items()}
                gaps = {name: abs(got[name] - want[name]) / abs(want[name]) for name in want}
                # Position by position, so that gaps of either sign do not cancel in the mean.
                gaps["greedy_by_position"] = float(jnp.mean(jnp.abs(got_nll["greedy"] - want_nll["greedy"]))) / abs(want["greedy"])
                line[precision] = {"system": got, "relative_gap": gaps, "inside": all(g <= tolerance for g in gaps.values())}
        finally:
            if patched:
                setattr(arch, *patched)
        line["inside"] = line["stated"]["inside"] and line["float32"]["inside"]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
