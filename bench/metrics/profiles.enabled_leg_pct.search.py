"""The super-table's legs that the window's members enabled, in percent: the
``profiles.enabled_legs`` counter over ``profiles.members`` times the
super-table's legs (program counters, their change over the window). A
member enables one leg for a remote access or a stage-in, two for a
placement."""
from harness.readers import counter


def read(run):
    legs, members = counter(run, "profiles.enabled_legs"), counter(run, "profiles.members")
    width = counter(run, "profiles.legs")
    if legs is None or not members or not width:
        return None
    return 100.0 * legs / (members * width)
