"""Exact decimal text, as the MySQL wire carries it, from scaled ints."""


def dec(scaled: int, scale: int) -> str:
    """`scaled` / 10**scale with exactly `scale` fraction digits."""
    sign = "-" if scaled < 0 else ""
    whole, frac = divmod(abs(int(scaled)), 10 ** scale)
    return f"{sign}{whole}.{frac:0{scale}d}" if scale else f"{sign}{whole}"


def avg(total: int, count: int, scale: int) -> str:
    """AVG of a decimal(.., scale) column: exact, scale + 4 digits,
    rounded half away from zero (MySQL's div_precision_increment)."""
    num = abs(int(total)) * 10 ** 4
    q = (2 * num + count) // (2 * count)
    return dec(-q if total < 0 else q, scale + 4)
