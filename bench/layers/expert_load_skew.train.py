"""How unevenly the held experts are loaded: per training step in the
window, the most token-expert pairs one held expert computed in one MoE
layer (``expert_load_max``) over the mean load of a held expert in a
layer (``expert_tokens`` / (MoE layers x experts held)), both counters
of the launcher's ``train.sync`` span; the mean over the window's steps.
1 is an even load.  Under expert parallelism the busiest expert's chip
sets the step."""

import program_spans


def read(run):
    syncs = program_spans.spans_in(run, "train.sync")
    if not syncs:
        return None
    m = run.cell.config["model"]
    slots = (m["num_hidden_layers"] - m["first_k_dense_replace"]) \
        * m["n_routed_experts"]
    skew = [s.counts["expert_load_max"] * slots / s.counts["expert_tokens"]
            for s in syncs if s.counts.get("expert_tokens")]
    return sum(skew) / len(skew) if skew else None
