from repro.utils.metrics import (  # noqa: F401
    avg_f1_score,
    best_f1_per_cluster,
    canonical_labels,
    label_agreement,
    matched_agreement,
)
