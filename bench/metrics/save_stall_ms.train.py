"""Host milliseconds the training loop spends inside each save
(`Trainer._save_ckpt`, spanned by the benchmark)."""


def read(view):
    s = view["records"]["save_s"]
    return 1e3 * sum(s) / len(s) if s else None
