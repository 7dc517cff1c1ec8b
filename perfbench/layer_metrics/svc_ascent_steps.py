"""Steps the exact-dual SVC ascent ran a lane: the program's
``tpuml_svm_ascent_steps_total{solver="exact"}`` after the window over the
lanes the run fitted (the first search's and the window's: trials x (folds
+ 1) each). Every search of a run fits the same trials on the same rows, so
this is also the window's. A lane stops at its KKT stop or at the cap (600),
so the number says how much of the cap the drawn ``C`` values use. A program
without the counter reads 0 and gives nothing."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "steps", "program_counter", "trials_per_s"


def read(ctx):
    from cs230_distributed_machine_learning_tpu.obs import REGISTRY

    steps = REGISTRY.counter("tpuml_svm_ascent_steps_total").value(solver="exact")
    traffic = ctx["cell"]["traffic"]
    lanes = int(traffic["n_iter"]) * (int(traffic["cv"]) + 1) * (1 + len(ctx["searches"]))
    return steps / lanes if steps > 0 else None
