"""Share of the IDMap's rounds that did work, over the window's steps and
the engine's groups, in %: the deepest round in which pass 1 found a key
and pass 2 placed one (``<group>/idmap_probe_depth``,
``<group>/idmap_claim_depth``) over the rounds the two passes ran
(``<group>/idmap_probe_rounds``, ``<group>/idmap_claim_rounds``), each the
largest over the chips. Nothing when no pass ran a round."""

DEPTHS = ("idmap_probe_depth", "idmap_claim_depth")
ROUNDS = ("idmap_probe_rounds", "idmap_claim_rounds")


def read(ctx):
    def total(names):
        return sum(step.get(f"{g}/{n}", 0.0) for step in ctx.history for g in ctx.budgets
                   for n in names)

    rounds = total(ROUNDS)
    return 100.0 * total(DEPTHS) / rounds if rounds else None
