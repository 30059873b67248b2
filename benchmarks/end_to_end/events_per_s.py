"""Source events the connector was handed inside the window, a second
(all chips of the cell together).  Host clock, the client's side."""


def read(run):
    return run.delivered_in_window() / run.seconds
