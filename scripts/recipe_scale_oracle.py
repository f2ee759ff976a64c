"""Recipe-SCALE convergence oracle: the reference's full training shape —
multi-epoch linear warmup + long cosine decay — executed end-to-end through
the production trainer, not just unit-tested as schedule math.

The reference's published recipes train 100 epochs with 5-epoch warmup
(`/root/reference/config/*.yaml`); its accuracy table is the evidence the
recipe *runs*. ImageNet is unreachable from this box, so this executes the
identical recipe SHAPE (OPTIM.WARMUP_EPOCHS=5, cosine over MAX_EPOCH=100,
SGD+momentum+weight-decay, SyncBN, full augmentation, periodic checkpoints
with auto-resume) on the bundled sklearn-digits ImageFolder — every
component at its production setting except the dataset. It delegates to
``tutorial/real_data_oracle.main`` so there is exactly one copy of the
digits recipe. ~2 h on the 1-core CPU host; minutes on a TPU chip.

Run:

    python scripts/cpu_mesh_run.py scripts/recipe_scale_oracle.py
    # transcript lands in the per-user digits cache under
    # out_recipe_{epochs}x{warmup}/ (rank-0 log file)

AUTO_RESUME is on (a 2 h run should survive interruption), and the OUT_DIR
is scoped by (epochs, warmup) so changing the arguments never resumes a
mismatched checkpoint. Re-running after a COMPLETED run resumes past
MAX_EPOCH and reports the stored best without training — delete the out
dir to start over (the script prints which).

Recorded run 2026-07-31 (8-dev CPU mesh, seed 1): best val Acc@1 96.0 at
epoch 60, 95.7 at epoch 100; warmup LR 0.005->0.0497 then cosine->1.2e-5;
87 min wall. The band below is calibrated from that run with an 11-point
margin.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "tutorial")
)

RECIPE_MIN_ACC1 = 85.0


def main(epochs: int = 100, warmup: int = 5) -> float:
    import getpass
    import tempfile

    import real_data_oracle

    root = os.path.join(
        tempfile.gettempdir(), f"dtpu_digits_recipe_{getpass.getuser()}"
    )
    out_name = f"out_recipe_{epochs}x{warmup}"
    print(f"recipe-scale oracle: OUT_DIR={os.path.join(root, out_name)}", flush=True)
    best = real_data_oracle.main(
        root=root,
        epochs=epochs,
        warmup=warmup,
        auto_resume=True,
        out_name=out_name,
    )
    status = "OK" if best >= RECIPE_MIN_ACC1 else "FAILED"
    print(
        f"RECIPE-SCALE {status}: best val Acc@1 {best:.1f} "
        f"(band: >= {RECIPE_MIN_ACC1:.0f}; warmup {warmup} + cosine {epochs})"
    )
    return best


if __name__ == "__main__":
    acc = main(
        epochs=int(sys.argv[1]) if len(sys.argv) > 1 else 100,
        warmup=int(sys.argv[2]) if len(sys.argv) > 2 else 5,
    )
    sys.exit(0 if acc >= RECIPE_MIN_ACC1 else 1)
