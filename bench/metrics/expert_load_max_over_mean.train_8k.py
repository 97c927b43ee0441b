"""The held experts' load imbalance: the largest of their mean per-step
assignment counts (the step's ``expert_counts`` counter, summed over the
expert layers, averaged over the window's steps) over the mean of them.
1.0 is an even load.  Nothing where the step returns no counter."""


def read(rec):
    counts = rec["window"]["record"].get("expert_counts")
    if not counts or sum(counts) <= 0:
        return None
    return max(counts) / (sum(counts) / len(counts))
