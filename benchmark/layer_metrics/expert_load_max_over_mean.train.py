"""How uneven the routing was over the run: in each expert layer the slots of
the fullest held expert over the mean of the held experts, the largest over
the layers. From the counts the program keeps on the device and hands over
once after the window (``after_window()``: ``expert_slots``). 1.0 is an even
routing. Nothing where the program published no such counts."""
NAME = "expert_load_max_over_mean.train"
UNIT = "ratio"
LAYER = "model step"
MOVES = "train_samples_per_s"
SOURCE = "program_counter"


def read(run):
    layers = (run.get("program") or {}).get("expert_slots")
    if not layers:
        return None
    ratios = [max(row) * len(row) / float(sum(row)) for row in layers if sum(row) > 0]
    return max(ratios) if ratios else None
