"""Share of the causal (query, key) pairs that the attention kernels were
handed as selected, over the run: the pairs the program's indexers counted on
the device (``selected_pairs``, read once after the window:
``after_window()``) over the causal pairs of the steps counted, summed over
layers and sequences. In percent. 100 says the selection never engaged;
``min(t + 1, topk)`` a row gives 43.75 at 8192 under a top-2048. Nothing where
the program published no such counts."""
NAME = "attention_pairs_kept_share.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_samples_per_s"
SOURCE = "program_counter"


def read(run):
    counts = run.get("program") or {}
    if not counts.get("causal_pairs") or counts.get("selected_pairs") is None:
        return None
    return 100.0 * counts["selected_pairs"] / counts["causal_pairs"]
