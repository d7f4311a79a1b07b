"""Model step: device time of the output head (`head`: final norm and the
vocabulary-wide product) and of the sampler (`sample`: penalty pass,
warpers, the choice, the slots' state) per scheduler step. Together: the
compiler fuses the penalty pass into the head's product as its epilogue."""
from harness import program_scopes


def read(ctx):
    return program_scopes.ms_per_step(ctx, ("head", "sample"))
