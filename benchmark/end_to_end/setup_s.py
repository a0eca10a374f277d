"""Process start to the first measured flow: loading, building the native
library where it is missing, making the stream, compilation or loading
from the compile cache, and the warm-up flows."""



def read(run):
    return run.t_first_flow - run.t_process
