"""How unevenly a query's kernel launches fall on the cell's chips:
the launches on each query's busiest chip (the program's
``dispatch_max_device_launches``), summed over the window, over all
launches (``dispatch_launches``), times the cell's chips. 1 is an even
spread, the number of chips is every launch on one chip; 0 where no
query launched a kernel."""
from bench.program_counters import recorded


def read(run):
    """Busiest-chip launches over all launches, times the chips."""
    busiest = recorded(run, "dispatch_max_device_launches")
    launches = recorded(run, "dispatch_launches")
    if busiest is None or launches is None:
        return None
    if not launches:
        return 0.0
    return busiest / launches * run.cell.get("chips", 1)
