"""Layer "compile": programs that reached the backend's compiler
inside the window (jax.monitoring). Anything but 0 makes the run
incorrect: nothing compiles in the window."""


def read(obs):
    return obs.get("programs_compiled")
