"""Ling-3.0-flash's loss in the system against the plain reference at the
published widths, on the chip, once, outside any window (``PERF.md`` section 6
has the numbers).

    chiprun -- python3 perfbench/tests/reference_on_chip_bailing_hybrid.py --seed N [--break KIND ...] [--layers N] [--platform cpu --tiny]

``ling-3.0-flash-ep32`` as the cell runs it (the dense layer and the period of
sparse ones, five of them delta attention and one latent attention; the 16
experts and the vocabulary slice held; the cell's micro-batch and ``seq_len``;
``--layers`` for fewer), seeded weights by the architecture's own rule, one
batch. The same two losses as ``reference_on_chip.py``, system against
reference (float32, ``highest`` precision, the delta attention one position at
a time, the latent attention in blocks of queries so that it fits):

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
large faults, the second sees the mathematics with no rounding in its way:
of the pairs a token sends to its experts a thirty-second reach the 16 held
here, so a fault of the routing moves the loss less than bf16's rounding does
and only the float32 comparison can see it. A kind runs the system
with a part changed (``scalar_decay``: the decay averaged to one scalar a head,
a gated DeltaNet under this model's name; ``softplus_gate``: ``-exp(A_log)
softplus(.)`` in place of the bounded gate; ``bias_out_of_the_choice``;
``bias_in_the_weights``; ``group_limit``: a plain top-8 over all 512 experts;
``head_gate``: the latent attention's gate a head left out; ``shared_expert``)
or with its weights rounded through float8_e4m3fn, the nearest precision below
the bf16 the configuration states (``fp8``): each has to fall outside one of
the two tolerances, and the sound system inside both.
"""

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# Relative, on each gap, all seven layers (PERF.md section 6, PR 34; four seeds of the sound system, one with every
# kind). In the stated dtypes: the sound system's largest gap is 2.77e-3 (by position; 2.52e-3 to 2.77e-3 over four
# seeds), float8 weights read 0.0389 and the smallest faulty kind this comparison can see, the bias left out of the
# choice, 0.0198; 1e-2 lies between (the geometric mean of 2.77e-3 and 0.0389 is 1.04e-2). The plain top-8 (7.3e-3),
# the head gate left out (3.8e-3) and the bias let into the weights (3.1e-3) drown in bf16's rounding there: the held
# experts see a thirty-second of the pairs, and the latent attention of one layer in seven averages over thousands
# of positions. In float32 nothing is rounded away: the sound system reads 6.7e-7 to 6.9e-7 and the smallest faulty
# kind, the bias let into the weights, 1.50e-3 (8.9e-4 on a fifth seed; head gate 2.5e-3, plain top-8 6.6e-3); 3e-5 is the
# geometric mean of 6.9e-7 and 1.50e-3.
TOLERANCE = {"stated": 1e-2, "float32": 3e-5}
BROKEN = {  # the function of the architecture to replace, and the control it is called with
    "scalar_decay": ("kda", {"scalar_decay": True}),
    "softplus_gate": ("kda", {"softplus_gate": True}),
    "bias_out_of_the_choice": ("expert_layer", {"bias_in_choice": False}),
    "bias_in_the_weights": ("expert_layer", {"bias_in_weights": True}),
    "group_limit": ("expert_layer", {"group_limit": False}),
    "head_gate": ("mla", {"head_gate": False}),
    "shared_expert": ("expert_layer", {"shared": False}),
}
KINDS = tuple(BROKEN) + ("fp8",)


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
    arch = run.find_architecture(ROOT, "bailing_hybrid")
    ref = run.load_module("pb_reference_bailing_hybrid", os.path.join(ROOT, "perfbench", "models", "reference", "bailing_hybrid.py"))
    cfg = run.load_json(ROOT, "perfbench", "configs", "ling-3.0-flash-ep32.json")
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
    sound = {name: getattr(arch, name) for name, _ in BROKEN.values()}
    for kind in [None] + args.kinds:
        for name, function in sound.items():
            setattr(arch, name, function)
        weights = params
        if kind == "fp8":
            weights = jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params)
        elif kind:
            name, control = BROKEN[kind]
            setattr(arch, name, lambda c, p, x, f=sound[name], control=control: f(c, p, x, **control))
        line = {"device": jax.devices()[0].device_kind, "seed": args.seed, "broken": kind,
                "layers": cfg["num_hidden_layers"], "tokens": list(inputs.shape), "reference": want, "tolerance": TOLERANCE}
        for precision, tolerance in TOLERANCE.items():
            exact = precision == "float32"
            with jax.default_matmul_precision("highest") if exact else contextlib.nullcontext():
                nll = jax.jit(lambda p, targets: arch.token_nll(cfg, p, inputs, targets))
                cast = jax.tree.map(lambda a: a.astype(jnp.float32), weights) if exact else weights
                got_nll = {"shifted": nll(cast, shifted), "greedy": nll(cast, greedy)}
            got = {name: float(jnp.mean(x)) for name, x in got_nll.items()}
            gaps = {name: abs(got[name] - want[name]) / abs(want[name]) for name in want}
            # Position by position, so that gaps of either sign do not cancel in the mean.
            gaps["greedy_by_position"] = float(jnp.mean(jnp.abs(got_nll["greedy"] - want_nll["greedy"]))) / abs(want["greedy"])
            line[precision] = {"system": got, "relative_gap": gaps, "inside": all(g <= tolerance for g in gaps.values())}
        line["inside"] = line["stated"]["inside"] and line["float32"]["inside"]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
