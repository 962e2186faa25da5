"""LFM2-24B-A2B's loss in the system against the plain reference at the
published widths, on the chip, once, outside any window (``PERF.md`` section 6
has the numbers).

    chiprun -- python3 perfbench/tests/reference_on_chip_lfm2_moe.py --seed N [--break KIND ...] [--layers N] [--grad-tokens T] [--platform cpu --tiny]

``lfm2-24b-a2b-ep8`` as the cell runs it (the nine layers ``conv conv attention
conv conv conv attention conv conv``, the first over a dense MLP and eight over
experts; the 8 experts and the vocabulary slice held; the cell's micro-batch and
``seq_len`` 8192; ``--layers`` for fewer), seeded weights by the architecture's
own rule, one batch. The same two losses as ``reference_on_chip.py``, system
against reference (float32, ``highest`` precision, the framework's grouped
convolution, one score matrix an attention layer under the explicit mask,
computed a block of query rows at a time so that it fits):

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
the pairs a token sends to its experts an eighth reach the 8 held here, so a
fault of the routing moves the loss less than bf16's rounding does). A kind
runs the system with a part changed (``BROKEN``), with its bf16 weights rounded
through float8_e4m3fn, the nearest precision below the bf16 the configuration
states (``fp8``), or with its float32 leaves (the router and its bias) rounded
through bf16 (``f32_as_bf16``): each has to fall outside one of the two
tolerances, and the sound system inside both.

``--grad-tokens T`` adds the gradients: the loss of the batch's first ``T``
positions (more than ``QUERY_BLOCK``, so that several blocks of the attention
are live on the way back) differentiated in the reference and three times in
the system: with float32 weights at ``highest`` precision (held to
``GRAD_TOLERANCE``), in the stated dtypes, and in the stated dtypes **with each
token's experts pinned to the reference's choice** (``route``'s ``chosen``). A
line with every leaf's gap in each: the largest as a share of the reference
gradient's largest element, and the gap's norm over the reference's. A token
whose fourth and fifth score lie within bf16's rounding of each other goes to
another expert in the stated dtypes than in the reference; pinned, what is left
is the rounding of the products, and that reading's worst norm is held to
``STATED_GRAD_TOLERANCE``.
"""

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# Relative, on each gap, all nine layers at 2 x 8192 tokens (my chip runs, PR 50, four seeds; PERF.md section 6 has each).
# Stated dtypes: the sound system reads 2.66e-3 to 2.88e-3 (the greedy loss position by position: bf16's 8 bits over nine
# layers' roundings) and its weights through float8 3.79e-2 to 3.86e-2: 8e-3 leaves 2.8 times of room above the one and 4.7
# below the other. The faults of the attention and of the routing that move a few thousandths (the per-head norms left out
# 3.4e-3 to 3.7e-3, put after the rotation 2.8e-3 to 3.0e-3, the bias let into the weights 3.2e-3 to 3.8e-3) drown in bf16's
# rounding there: two layers in nine attend, and an eighth of a token's pairs reach the held experts. Float32 at
# ``highest``: the sound system reads 1.2e-7 to 1.8e-7 (the order of the sums), the float32 leaves through bf16 2.7e-4 to
# 3.2e-4, the norms after the rotation 0.9e-3 to 1.0e-3, the bias in the weights 1.8e-3 to 2.5e-3, every other part 2.1e-3 or
# more (a ``conv`` layer's gates, taps or norm 0.42-0.58: seven layers in nine are such): 5e-5, 280 times over the one and
# 5.4 under the nearest of the others.
TOLERANCE = {"stated": 8e-3, "float32": 5e-5}
# A leaf's gradient against the reference's, the largest gap as a share of the reference's largest element, with the
# system's weights cast to float32 and its products at ``highest`` precision. Both sides hand a bf16 leaf its gradient
# in bf16 (2^-9 of an element each, so 8e-3 at most of the largest; PERF.md section 6 has the chip's readings): 2e-2.
GRAD_TOLERANCE = 2e-2
# In the stated dtypes with the choices pinned to the reference's, the norm of a leaf's gap over the norm of the
# reference's gradient, the worst leaf: bf16's rounding of nine layers' products there and back, read 3.6e-2 at 1024
# positions (a per-head norm's gain; seed 5000000502; every stack and router under 2.8e-2). Unpinned the routers read up
# to 0.29 and the stacks and the norms before them up to 0.20: tokens whose fourth and fifth score round the other way go
# to another expert. 0.1 leaves 2.8 times of room above the one and 2.9 below the other.
STATED_GRAD_TOLERANCE = 0.1
# The function of the architecture to replace, and the control it is called with.
BROKEN = {
    "no_conv": ("short_conv", {"conv": False}),  # v = u: the three taps left out
    "no_b_gate": ("short_conv", {"b_gate": False}),
    "no_c_gate": ("short_conv", {"c_gate": False}),
    "taps_reversed": ("short_conv", {"reverse_taps": True}),  # the first tap the position's own
    "conv_rotated": ("short_conv", {"rotate": True}),  # a position given to a layer that takes none
    "no_qk_norm": ("attention", {"qk_norm": False}),
    "qk_norm_after_rotation": ("attention", {"norm_first": False}),
    "not_rotated": ("attention", {"rotate": False}),
    "no_operator_norm": ("layer", {"operator_norm": False}),
    "bias_out_of_the_choice": ("expert_layer", {"bias_in_choice": False}),
    "bias_in_the_weights": ("expert_layer", {"bias_in_weights": True}),
    "norm_topk_prob": ("expert_layer", {"norm_topk_prob": False}),
    "untied_head": ("head_nll", {"tied": False}),  # the head read from another matrix than the table
}
KINDS = tuple(BROKEN) + ("fp8", "f32_as_bf16")


def broken(arch, kind):
    """``arch``'s function that ``kind`` replaces, by name, with the control
    bound; the caller puts it in the module and takes it out again."""
    name, control = BROKEN[kind]
    sound = getattr(arch, name)
    return name, lambda *args, **kwargs: sound(*args, **kwargs, **control)


def rounded(params, kind):
    """The weights as ``fp8`` / ``f32_as_bf16`` would hold them."""
    import jax
    import jax.numpy as jnp

    if kind == "fp8":
        return jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params)
    return jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(a.dtype) if a.dtype == jnp.float32 else a, params)


def reference_choices(ref, cfg, params, inputs, held, block):
    """The experts the reference chooses for every token of ``inputs``: one
    ``(tokens, num_experts_per_tok)`` array a sparse layer, in the layers' order."""
    import jax

    seen, sound = [], ref.gate

    def recording(cfg, p, x):
        weights, chosen = sound(cfg, p, x)
        seen.append(chosen)
        return weights, chosen

    def choices(p):
        del seen[:]
        ref.logits(cfg, p, inputs, held, block)
        return list(seen)

    ref.gate = recording
    try:
        return jax.jit(choices)(params)
    finally:
        ref.gate = sound


@contextlib.contextmanager
def pinned(arch, choices):
    """``arch.expert_layer`` handed ``choices`` one a call, in the layers' order
    (a layer is traced once, under its ``jax.checkpoint``), and every one used."""
    left, sound = iter(choices), arch.expert_layer
    arch.expert_layer = lambda cfg, p, x: sound(cfg, p, x, chosen=next(left))
    try:
        yield
        assert next(left, None) is None, "fewer sparse layers traced than the reference chose for"
    finally:
        arch.expert_layer = sound


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--break", dest="kinds", choices=KINDS, nargs="*", default=[])
    parser.add_argument("--layers", type=int, help="the first so many layers (default: all the configuration has)")
    parser.add_argument("--grad-tokens", type=int, default=0, help="positions of the gradients' comparison (0: none)")
    parser.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    from perfbench import run, trainstate

    run.configure_compile_cache()  # before jax starts: kinds that change only the weights run the sound system's programs
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != args.platform:
        raise SystemExit(f"jax found {jax.devices()[0].platform!r}, not {args.platform!r}")
    arch = run.find_architecture(ROOT, "lfm2_moe")
    ref = run.load_module("pb_reference_lfm2_moe", os.path.join(ROOT, "perfbench", "models", "reference", "lfm2_moe.py"))
    cfg = run.load_json(ROOT, "perfbench", "configs", "lfm2-24b-a2b-ep8.json")
    if args.tiny:
        cfg.update(arch.TINY, job=dict(cfg["job"], seq_len=100))
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
    about = {"device": jax.devices()[0].device_kind, "seed": args.seed, "layers": cfg["num_hidden_layers"]}
    for kind in [None] + args.kinds:
        weights, patched = params, None
        if kind in ("fp8", "f32_as_bf16"):
            weights = rounded(params, kind)
        elif kind:
            name, function = broken(arch, kind)
            patched = (name, getattr(arch, name))
            setattr(arch, name, function)
        line = dict(about, broken=kind, tokens=list(inputs.shape), reference=want, tolerance=TOLERANCE)
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
    if args.grad_tokens:
        del want_nll
        short = tokens[:, :args.grad_tokens + 1]
        want_grads = jax.jit(jax.grad(lambda p: ref.loss(cfg, p, short, held, block)))(params)

        def gap(g, w):
            """(largest gap as a share of the reference's largest element, norm of the gap over the reference's norm)."""
            g, w = g.astype(jnp.float32), w.astype(jnp.float32)
            return jnp.stack([jnp.max(jnp.abs(g - w)) / jnp.maximum(jnp.max(jnp.abs(w)), 1e-30),
                              jnp.linalg.norm(g - w) / jnp.maximum(jnp.linalg.norm(w), 1e-30)])

        choices = reference_choices(ref, cfg, params, short[:, :-1], held, block)
        line = dict(about, gradients_of=list(short.shape), tolerance={"float32": GRAD_TOLERANCE, "stated_pinned": STATED_GRAD_TOLERANCE})
        for precision in ("float32", "stated", "stated_pinned"):
            exact = precision == "float32"
            with contextlib.ExitStack() as stack:
                if exact:
                    stack.enter_context(jax.default_matmul_precision("highest"))
                if precision == "stated_pinned":
                    stack.enter_context(pinned(arch, choices))
                cast = (lambda p: jax.tree.map(lambda a: a.astype(jnp.float32), p)) if exact else (lambda p: p)
                grads = jax.jit(jax.grad(lambda p: arch.loss_fn(cfg, cast(p), short)))(params)
            gaps = jax.tree_util.tree_flatten_with_path(jax.jit(lambda a, b: jax.tree.map(gap, a, b))(grads, want_grads))[0]
            del grads
            # The buffer that steers the choice has no gradient on either side.
            gaps = {trainstate.path_str(p): [float(x) for x in g] for p, g in gaps if not trainstate.path_str(p).endswith("expert_bias")}
            worst = {name: max(gaps, key=lambda leaf, i=i: gaps[leaf][i]) for i, name in enumerate(("largest_element", "norm"))}
            line[precision] = {"leaves": len(gaps), "worst": {name: [leaf, gaps[leaf][i]] for i, (name, leaf) in enumerate(worst.items())}, "relative_gap": gaps}
        line["inside"] = (line["float32"]["worst"]["largest_element"][1] <= GRAD_TOLERANCE
                          and line["stated_pinned"]["worst"]["norm"][1] <= STATED_GRAD_TOLERANCE)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
