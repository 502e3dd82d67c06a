"""The training "traffic" generator: a mix file for a training cell only
says how the trainer is invoked (mode, mesh, batch per chip, epochs to
warm up and to trace); the data itself is made by the configuration's
loader from the seed.  ``generate`` turns the mix into the command-line
arguments a user would type after ``python -m veles_tpu <workflow>``."""


def generate(mix, seed, chips):
    argv = ["--mode", mix["mode"], "--random-seed", str(int(seed))]
    if chips > 1:
        argv += ["--mesh", "%s=%d" % (mix["mesh_axis"], chips)]
    return {"argv": argv,
            "minibatch": mix["minibatch_per_chip"] * chips}
