"""Model FLOP utilisation of deployed inference over the traced window: the
configuration's model FLOPs per image times the images of every deployed
forward the trace holds, over the window times the chip's bf16 peak."""
LAYER = "model step (models/lm.py, models/snn_cnn.py)"
SOURCE = "device_trace"
MOVES = "deploy_images_per_s"
UNIT = "%"
PROGRAM = "jit_classify_batch"


def read(run):
    s = run.summary
    calls = sum(c for name, (_, c) in s.modules.items() if PROGRAM in name)
    if not calls or s.window_ns <= 0:
        return None
    flops = calls * run.params["batch"] * \
        run.config.deploy_flops_per_image(run.sizes)
    return 100.0 * flops / (s.window_s * run.peaks["bf16_flops"])
