"""Tokens per routed expert for the DeepSeek-V2 configuration, drawn once
and written into its file as data.

For MoE layer ``l`` (0-based among the MoE layers) the stream is
``numpy.random.default_rng([16, l])``. Each of the 160 experts gets a bias
b ~ N(0, 0.5^2); each token scores every expert as b plus standard Gumbel
noise and is routed by DeepSeek-V2's group-limited greedy top-k: the
experts form 8 groups of 20, the token keeps the 3 groups whose best
expert scores highest, then its 6 best experts inside them. The counts
are the ``bincount`` of those choices, so each layer's sum is 6 x tokens.

    python3 bench/tools/expert_tokens.py --tokens 4096 --layers 4

prints the lists as JSON; ``--check <config.json>`` exits nonzero unless
the configuration's ``workload.expert_tokens`` equals the draw.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

EXPERTS, GROUPS, TOPK_GROUPS, TOPK = 160, 8, 3, 6
BIAS_SD = 0.5


def layer_counts(layer: int, tokens: int) -> list[int]:
    """One MoE layer's tokens per expert."""
    rng = np.random.default_rng([16, layer])
    bias = rng.normal(0.0, BIAS_SD, EXPERTS)
    scores = bias[None, :] + rng.gumbel(size=(tokens, EXPERTS))
    by_group = scores.reshape(tokens, GROUPS, EXPERTS // GROUPS)
    keep = np.argsort(-by_group.max(axis=-1), axis=-1)[:, :TOPK_GROUPS]
    allowed = np.zeros((tokens, GROUPS), dtype=bool)
    np.put_along_axis(allowed, keep, True, axis=-1)
    masked = np.where(np.repeat(allowed, EXPERTS // GROUPS, axis=-1),
                      scores, -np.inf)
    chosen = np.argsort(-masked, axis=-1)[:, :TOPK]
    return np.bincount(chosen.ravel(), minlength=EXPERTS).tolist()


def draw(tokens: int, layers: int) -> list[list[int]]:
    return [layer_counts(layer, tokens) for layer in range(layers)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tokens", type=int, default=4096)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--check", help="a configuration file to compare")
    args = ap.parse_args(argv)
    if args.check:
        with open(args.check) as f:
            wl = json.load(f)["workload"]
        want = draw(wl["tokens"] * wl["batch"], wl["layers_moe"])
        same = wl["expert_tokens"] == want
        print("expert_tokens " + ("match" if same else "differ"))
        return 0 if same else 1
    print(json.dumps(draw(args.tokens, args.layers)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
