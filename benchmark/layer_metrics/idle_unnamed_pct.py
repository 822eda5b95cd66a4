from _scopes import idle_unnamed_pct as read  # noqa: F401  idle-gap seconds in no program span, of all
