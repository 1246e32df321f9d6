from repro.kernels.scanfold.ops import (scan_fold_completions,  # noqa: F401
                                        scan_fold_end_time)
