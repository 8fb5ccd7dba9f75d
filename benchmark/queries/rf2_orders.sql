delete from orders
where o_orderkey between (select min(o_orderkey) from orders)
                     and (select min(o_orderkey) + 7499 from orders)
