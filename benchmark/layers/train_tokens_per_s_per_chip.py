"""Tokens a second a chip in the median pass: the rate that
``train_images_per_s_per_chip`` gives in packed sequences, times the tokens
of a sequence (host clock). Layer: Step."""


def read(run):
    c = run.counters
    if "tokens_per_image" not in c or "median_pass_s" not in c:
        return None
    return c["images_per_pass"] * c["tokens_per_image"] \
        / c["median_pass_s"] / c["chips"]
