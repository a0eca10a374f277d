"""Rows the sketch families dropped because their unit had closed: the
program's own gauge late_flows_dropped, summed over the models, at the end
of the run. The configuration's guarantee is 0 (and the run's `failed`
counts every one). Source: the program's metric registry."""

NAME = "late_flows_dropped"


def read(run):
    from flow_pipeline_tpu.obs import REGISTRY

    return sum(float(line.rsplit(" ", 1)[1])
               for line in REGISTRY.render().splitlines()
               if line.startswith(NAME) and line[len(NAME)] in " {")
