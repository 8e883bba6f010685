"""Device ms per update inside the model's scope ``model.embed``:
the per-observation front of a sequence Q-network, run once over every frame
of the update outside any time loop: R2D2's conv torso (cast, normalise,
Conv_0-2, Dense_0), the hybrid trunk's frame embedding (Dense 7,056 -> 2,688).
Target pass, online pass and its backward together: self time of the step
program's ops whose ``tf_op`` path holds that scope innermost among the
model's (harness/model_scopes.py).  Cuts the time of ``phase_target_ms`` +
``phase_online_ms`` another way; does not add to the eight phases: in R2D2
those two less this is what the recurrence (the LSTM scans) and the loss
still cost.  None where the program names no such scope."""

from ..harness import model_scopes

METRIC = {"layer": "fused_step", "unit": "ms", "better": "lower",
          "source": "program_span", "moves": "updates_per_s"}


def read(ctx):
    return model_scopes.read(ctx, "embed")
