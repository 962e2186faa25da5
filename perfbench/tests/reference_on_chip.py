"""The system's loss against the plain reference at the published widths, on
the chip, once, outside any window (``PERF.md`` section 6 has the numbers).

    chiprun -- python3 perfbench/tests/reference_on_chip.py --seed N [--break KIND] [--platform cpu --tiny]

One period of ``qwen3-next-80b-a3b-ep16`` (4 layers, the 32 experts and the
vocabulary slice held), seeded weights by the architecture's own rule, one
batch of ``seq_len`` tokens. Two losses are compared, system against reference
(float32, ``highest`` precision, attention in blocks of queries so that it
fits):

``shifted``  the training loss: each position's target is the next token. With
             random weights and random targets this is ``log(rows) + var/2`` of
             the logits whatever the mixers compute, so it is blind to them.
``greedy``   the same model on the reference's own most likely next tokens.
             A hidden state that turns away from the reference's loses the
             largest logit, so this one sees every part of every layer. Its gap
             is given twice: of the means, and as the mean over positions of
             the absolute gap (``greedy_by_position``), where nothing cancels.

``--break`` runs the system with a part taken out (``shared_expert``,
``output_gate``: the ``sigmoid(gate)`` on the attention layers' output and
nothing else, ``decay``) or with its weights rounded through float8_e4m3fn,
the nearest precision below the bf16 the configuration states (``fp8``): each
has to fall outside the tolerance that the sound system meets.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# Relative, on each gap: bf16 weights and activations against float32. Between
# the largest gap the sound system read over its seeds (4.5e-3, by position)
# and the smallest a part left out gave (0.023, the attention output gate
# alone), at their geometric mean (PERF.md section 6, PR 28).
TOLERANCE = 1e-2
KINDS = ("shared_expert", "output_gate", "decay", "fp8")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--break", dest="kind", choices=KINDS)
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
    arch = run.find_architecture(ROOT, "qwen3_next")
    ref = run.load_module("pb_reference_qwen3_next", os.path.join(ROOT, "perfbench", "models", "reference", "qwen3_next.py"))
    cfg = run.load_json(ROOT, "perfbench", "configs", "qwen3-next-80b-a3b-ep16.json")
    cfg = dict(cfg, num_hidden_layers=cfg["full_attention_interval"])
    if args.tiny:
        cfg.update(arch.TINY, job=dict(cfg["job"], seq_len=96))
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
    if args.kind == "shared_expert":
        layer = arch.expert_layer
        arch.expert_layer = lambda c, p, x: layer(c, p, x, shared=False)
    elif args.kind == "output_gate":
        attention = arch._attention
        arch._attention = lambda c, p, x: attention(c, p, x, output_gate=False)
    elif args.kind == "decay":
        rule = arch.chunked_delta_rule
        arch.chunked_delta_rule = lambda q, k, v, g, beta: rule(q, k, v, 0.0 * g, beta)
    elif args.kind == "fp8":
        params = jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params)
    nll = jax.jit(lambda p, targets: arch.token_nll(cfg, p, inputs, targets))
    got_nll = {"shifted": nll(params, shifted), "greedy": nll(params, greedy)}
    got = {name: float(jnp.mean(x)) for name, x in got_nll.items()}
    gaps = {name: abs(got[name] - want[name]) / abs(want[name]) for name in want}
    # Position by position, so that gaps of either sign do not cancel in the mean.
    gaps["greedy_by_position"] = float(jnp.mean(jnp.abs(got_nll["greedy"] - want_nll["greedy"]))) / abs(want["greedy"])
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "seed": args.seed, "broken": args.kind,
        "layers": cfg["num_hidden_layers"], "tokens": list(inputs.shape), "system": got, "reference": want,
        "relative_gap": gaps, "tolerance": TOLERANCE, "inside": all(g <= TOLERANCE for g in gaps.values()),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
