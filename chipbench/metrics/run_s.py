"""run_s: time to solution of the placed program, the window's wall time
over the runs made in it (a closed loop, so the mean run time with
nothing left out)."""


def read(cell):
    if not cell.attempted or cell.window_s is None:
        return None
    return cell.window_s / cell.attempted
