"""Imbalance of the held experts: a step's largest per-expert load (the
program's ``train.moe`` ``load_max``, over every expert layer) over the mean
load per held expert and layer, the median over the window's steps."""
import statistics

from chiplib.program_spans import in_window


def read(r):
    c = r.config
    slots = c["n_routed_experts"] * (c["num_hidden_layers"]
                                     - c["first_k_dense_replace"])
    ratios = [a["load_max"] * slots / a["routed"]
              for a in (s.attrs for s in in_window(r, "train.moe"))
              if a["routed"]]
    return statistics.median(ratios) if ratios else None
