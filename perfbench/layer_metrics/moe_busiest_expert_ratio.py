"""The busiest held expert's token-expert pairs over the held experts'
mean, in the window: growth of ``/info`` ``moe_expert_pairs`` from its
open to its close.  1 under even routing; what a grouped matmul's
longest group costs over its mean."""


def read(ctx):
    a = ctx.collected["info_open"].get("moe_expert_pairs")
    b = ctx.collected["info_close"].get("moe_expert_pairs")
    if not a or not b or len(a) != len(b):
        return None
    grew = [y - x for x, y in zip(a, b)]
    if sum(grew) <= 0:
        return None
    return max(grew) * len(grew) / sum(grew)
