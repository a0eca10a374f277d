"""Keys the ranked tables hold at a checkpoint and did not hold at the one
before, all sketch families summed, over the device steps that fed the
tables in between: the table's churn a batch (a key that came and went
between two checkpoints is not seen; the checkpoint after a close finds
every table new). Median over the window's checkpoints. Source:
ckpt_state's hh_admitted ({family: keys}) and hh_steps; a program whose
ckpt_state does not say reads nothing."""

import statistics

from benchmark import program_spans


def read(run):
    w = program_spans.window(run)
    said = [s[5] for s in w.named("ckpt_state")
            if s[5].get("hh_steps")] if w else []
    return (statistics.median(sum(a["hh_admitted"].values()) / a["hh_steps"]
                              for a in said)
            if said else None)
